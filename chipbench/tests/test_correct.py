"""A whole run of the tiny cells on the CPU, the chip check skipped: the
sound program comes out correct; the program with a token altered where it
is produced comes out not correct; judged by the harness's own decision,
under the cell's limits, the float8 control comes out not correct where
the sound program comes out correct, also through ``control.py``."""

import jax
import jax.numpy as jnp
import pytest

from chipbench.lib import bench, counts, serve
from chipbench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(counts, "peaks", lambda kind: PEAKS)


def run(checkout, cell, seed, seconds=2.0):
    c = bench.load_cell(checkout, cell)
    args = bench.parse_args(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds)])
    return serve.run(c, args, jax.devices(), bench.CompileClock(), 0.0)


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.poisson"])
def test_sound_run_is_correct(checkout, cell):
    result, checks = run(checkout, cell, 2 ** 33 + 3)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    c = bench.load_cell(checkout, cell)
    assert set(result["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(checks) == ["logit_gap_max", "tokens_compared",
                            "failed_requests"]


def test_token_altered_where_produced_is_not_correct(checkout, monkeypatch):
    from repro.serve import engine

    real = engine.sample_tokens

    def altered(logits, **kw):
        tok = real(logits, **kw)
        return jnp.where(kw["positions"] % 5 == 3, (tok + 1) % 300, tok)

    engine._jitted_fns.cache_clear()
    monkeypatch.setattr(engine, "sample_tokens", altered)
    try:
        result, checks = run(checkout, "tiny.closed", 77)
    finally:
        engine._jitted_fns.cache_clear()
    assert not result["correct"]
    assert checks["logit_gap_max"]["value"] > checks["logit_gap_max"]["limit"]


def test_control_lies_past_the_limit(checkout):
    c = bench.load_cell(checkout, "tiny.closed")
    for seed in (5, 6, 7):
        args = bench.parse_args(["--workload", "tiny.closed", "--seed",
                                 str(seed), "--seconds", "1.5"])
        r = serve.limit_readings(c, args, jax.devices(),
                                 bench.CompileClock(), control=True)
        assert bench.passes(r["program"]), r
        assert not bench.passes(r["control"]), r
        assert r["control"]["logit_gap_max"]["value"] > \
            r["control"]["logit_gap_max"]["limit"]


def test_control_script_judges_each_side(checkout, monkeypatch, capsys):
    import json
    import runpy
    import sys
    monkeypatch.setattr(bench, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(sys, "argv", [
        "control.py", "--workload", "tiny.closed", "--seconds", "1.5",
        "--seeds", "8", "--control-seeds", "8,9"])
    runpy.run_path(str(checkout / "chipbench" / "control.py"),
                   run_name="__main__")
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["seed"] for ln in lines[:-1]] == [8, 9]
    assert lines[0]["program"]["correct"] and "program" not in lines[1]
    assert lines[-1]["correct"] == {"program": [True],
                                    "control": [False, False]}
