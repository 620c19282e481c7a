"""Operation and byte counts against hand counts at the cells' widths."""
import json
import pathlib

import pytest

from chipbench.lib import counts

CONFIGS = pathlib.Path(__file__).parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_parameter_counts():
    m = cfg("minicpm-2b")
    # 122753·2304 tied + 40·(2304·108·64 + 2304·2304 + 3·2304·5760 + 2·2304)
    # + 2304
    assert counts.param_count(m) == 2_724_880_896
    g = cfg("chatglm3-6b")
    # 2·65024·4096 + 28·(4096·36·128 + 4096·4096 + 3·4096·13696 + 2·4096)
    # + 4096
    assert counts.param_count(g) == 6_243_454_976


def test_kv_per_token():
    assert counts.kv_bytes_per_token(cfg("minicpm-2b")) == 360 * 1024
    assert counts.kv_bytes_per_token(cfg("chatglm3-6b")) == 28 * 1024


def test_decode_counts_by_hand():
    m = cfg("minicpm-2b")
    # weights streamed once: everything but nothing twice (tied head)
    assert counts.streamed_weight_bytes(m) == 2 * counts.param_count(m)
    g = cfg("chatglm3-6b")
    # untied: the input embedding is only looked up, not streamed
    assert counts.streamed_weight_bytes(g) == 2 * (
        counts.param_count(g) - 65024 * 4096)
    one = counts.decode_bytes(m, 1, [100])
    assert one == 2 * counts.param_count(m) + 100 * 360 * 1024 + 2 * 2304
    lp = 2304 * 108 * 64 + 2304 * 2304 + 3 * 2304 * 5760
    per_tok = 2 * (40 * lp + 2304 * 122753)
    assert counts.decode_flops(m, [100]) == per_tok + 4 * 36 * 64 * 40 * 100


def test_prefill_and_train_by_hand():
    g = cfg("chatglm3-6b")
    lp = 4096 * 36 * 128 + 4096 * 4096 + 3 * 4096 * 13696
    p = 512
    want = (2 * p * 28 * lp + 4 * 32 * 128 * 28 * p * (p + 1) / 2
            + 2 * 4096 * 65024)
    assert counts.prefill_flops(g, p) == pytest.approx(want)
    m = cfg("minicpm-2b")
    lp = 2304 * 108 * 64 + 2304 * 2304 + 3 * 2304 * 5760
    fwd = (2 * 8 * 1024 * (7 * lp + 2304 * 122753)
           + 4 * 36 * 64 * 7 * 8 * 1024 * 1025 / 2)
    m7 = dict(m, num_hidden_layers=7)
    assert counts.train_flops(m7, 8, 1024) == pytest.approx(3 * fwd)


def test_peaks_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
