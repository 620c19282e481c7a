"""The MLA + MoE cell's pieces at toy widths on the CPU: the float32
reference against the program's paged prefill then decode on weights that
``moe_weights`` draws, the counts of ``mla_counts`` by hand, the four
readers on hand-made readings, the mix, and whole runs of a tiny cell
through ``serve_moe`` (the sound program correct, the float8 control
not)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import (bench, counts, mla_counts, mla_reference,
                           moe_weights, reference, serve_moe, traffic)
from chipbench.tests import tiny

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY_V2 = {
    "name": "tiny-v2", "source": "test", "repo_config": "deepseek_v2_236b",
    "repo_overrides": {"num_layers": 3, "d_model": 64, "num_heads": 4,
                       "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                       "vocab_size": 256, "q_lora_rank": 32,
                       "kv_lora_rank": 32, "rope_head_dim": 8,
                       "moe_d_ff": 32, "num_experts": 8,
                       "experts_per_tok": 2, "n_group": 4, "topk_group": 2,
                       "experts_held": 5, "first_expert": 1,
                       "dtype": "float32"},
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 5,
    "n_routed_experts_published": 8, "first_expert_held": 1,
    "n_shared_experts": 2, "num_experts_per_tok": 2, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 16, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "vocab_size": 256,
    "tie_word_embeddings": False, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "engine": {"num_slots": 3, "page_size": 8, "max_seq": 64,
               "min_bucket": 8},
    "correct": {"logit_gap_mean": 0.001, "min_tokens": 20},
}
# The program computes in float32 here, as the reference does, so that a
# near-tie of the router that bfloat16 rounding decides (a whole expert
# swapped: a gap of tenths at these widths) does not stand between the
# sound program and a limit that the float8 control fails; what such
# near-ties do in bfloat16 is measured on the chip, at the cell's widths.
MIX = dict(tiny.CLOSED, kind="serve_moe")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dest = tiny.make_checkout(tmp_path_factory.mktemp("checkout"))
    (dest / "chipbench" / "configs" / "tiny-v2.json").write_text(
        json.dumps(TINY_V2))
    (dest / "chipbench" / "traffic" / "tiny-moe.json").write_text(
        json.dumps(MIX))
    b = json.loads((dest / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-v2.moe", "config": "tiny-v2",
                           "traffic": "tiny-moe", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "deepseek-v2.mla-decode-8k" in m.get("workloads", []):
            m["workloads"].append("tiny-v2.moe")
    (dest / "BENCHMARK.json").write_text(json.dumps(b))
    return dest


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(counts, "peaks", lambda kind: PEAKS)


def test_config_file_states_the_program():
    """The cell's file holds DeepSeek-V2's published keys; the program's
    configuration with the file's overrides agrees with every one."""
    from chipbench.lib.bench import ROOT
    spec = json.loads((ROOT / "chipbench" / "configs"
                       / "deepseek-v2-ep16-8l.json").read_text())
    cfg = serve_moe.model_config(spec)
    assert (cfg.num_layers, cfg.held_experts, cfg.num_experts,
            cfg.vocab_size) == (8, 10, 160, 12800)
    assert set(spec["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                    "vocab_size"}
    with pytest.raises(ValueError, match="disagree"):
        serve_moe.model_config(dict(spec, n_group=4))


def test_reference_matches_prefill_then_paged_decode():
    """Both in float32 on bf16-drawn weights: 2e-4 leaves room for the
    absorbed decode's other order of summation."""
    from repro.models import lm
    cfg = serve_moe.model_config(TINY_V2)
    seed = 2 ** 32 + 7
    params = moe_weights.make_params(cfg, seed)
    rng = np.random.default_rng(0)
    plen, steps, page = 11, 9, 4
    toks = rng.integers(0, 256, plen + steps).tolist()
    caches = lm.init_paged_cache(cfg, 1, 8, page)
    table = jnp.arange(8, dtype=jnp.int32)[None]
    t = np.zeros((1, 16), np.int32)
    t[0, :plen] = toks[:plen]
    logits, caches = lm.prefill(cfg, params, caches, {"tokens": jnp.asarray(t)},
                                page_table=table, page_size=page,
                                logit_index=jnp.asarray([plen - 1]))
    got = [np.asarray(logits[0, :256])]
    for i in range(steps - 1):
        logits, caches = lm.decode_step(
            cfg, params, caches, jnp.asarray([toks[plen + i]], jnp.int32),
            jnp.asarray([plen + i], jnp.int32), page_table=table,
            page_size=page)
        got.append(np.asarray(logits[0, :256]))
    hid, flips = mla_reference.final_hidden(
        TINY_V2, seed, [toks[:plen + steps - 1]], mla_reference.BLOCK_Q)
    w = reference.head(TINY_V2, seed)
    want = np.asarray(hid[0][plen - 1:plen + steps - 1] @ w)
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)
    assert flips[0].shape == (2, mla_reference.BLOCK_Q)
    # the float8 control is another model
    hid8, _ = mla_reference.final_hidden(
        TINY_V2, seed, [toks[:plen + steps - 1]], mla_reference.BLOCK_Q,
        "fp8")
    assert np.abs(np.asarray(hid8[0] - hid[0])).max() > 1e-3


def test_reference_at_bfloat16():
    """``rounding="bf16"`` keeps the weights as served and rounds what a
    bfloat16 program holds: its rows are bfloat16 values, off the float32
    reference by far less than the float8 control is."""
    seed, toks = 2 ** 32 + 9, np.arange(3, 120).tolist()
    run = {r: np.asarray(mla_reference.final_hidden(
        TINY_V2, seed, [toks], mla_reference.BLOCK_Q, r)[0][0])
        for r in (None, "bf16", "fp8")}
    b = run["bf16"]
    np.testing.assert_array_equal(
        b, np.asarray(jnp.asarray(b).astype(jnp.bfloat16), np.float32))
    w16, _ = mla_reference.layer_weights(TINY_V2, seed, 1, "bf16")
    w32, _ = mla_reference.layer_weights(TINY_V2, seed, 1)
    assert all(np.array_equal(w16[n], w32[n]) for n in w32)
    off16 = np.abs(b - run[None]).max()
    assert 0 < off16 < np.abs(run["fp8"] - run[None]).max() / 2


def test_reference_routing_and_yarn():
    """The reference's own routing and frequency table, by hand."""
    z = mla_reference.sizes(TINY_V2)
    logits = jnp.asarray([[0.0, 3.0, 2.9, 0.1, 0.2, 0.0, 2.5, 0.0]])
    gate = np.asarray(mla_reference.route(z, logits))[0]
    p = np.asarray(jax.nn.softmax(logits[0]))
    # groups {0,1} {2,3} {4,5} {6,7}: best 3.0, 2.9, 0.2, 2.5 → keep 0, 1
    assert set(np.flatnonzero(gate)) == {1, 2}
    np.testing.assert_allclose(gate[[1, 2]], 16 * p[[1, 2]], rtol=1e-6)
    v2 = json.loads((bench.ROOT / "chipbench" / "configs"
                     / "deepseek-v2-ep16-8l.json").read_text())
    f = mla_reference.inv_freq(v2)
    extra = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:10], extra[:10], rtol=1e-9)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-9)
    assert mla_reference.softmax_scale(v2) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)


def test_weights_drawn_again_alike():
    cfg = serve_moe.model_config(TINY_V2)
    p = moe_weights.make_params(cfg, 4321)
    wg = p["groups"][1][0]["moe"]["wg"]
    again = moe_weights.leaf(4321, "groups/1/0/moe/wg", wg.shape[1:], 1)
    np.testing.assert_array_equal(np.asarray(wg[1], np.float32),
                                  np.asarray(again, np.float32))
    # each expert is drawn as a dense FFN of its fan-in
    std = float(np.asarray(wg, np.float32).std())
    assert std == pytest.approx(1 / np.sqrt(64), rel=0.1)
    assert np.all(np.asarray(p["groups"][0][0]["mla"]["kv_norm"]) == 1)


def test_counts_by_hand():
    m = TINY_V2
    d, h, qr, kvr, rd, hd = 64, 4, 32, 32, 8, 16
    assert mla_counts.latent_row_bytes(m) == 2 * (kvr + rd)
    params = (d * qr + qr + qr * h * (hd + rd) + d * (kvr + rd) + kvr
              + kvr * h * 2 * hd + h * hd * d)
    assert mla_counts.mla_params(m) == params
    tok = 2 * (d * qr + qr * h * (hd + rd) + d * (kvr + rd)
               + 2 * h * hd * kvr + h * hd * d)
    key = 2 * h * (2 * kvr + rd)
    b, f = mla_counts.mla_decode(m, 2, [5, 7])
    assert b == 2 * 2 * 3 * params + 3 * 2 * (kvr + rd) * 12
    assert f == 3 * (2 * tok + key * 12)
    fixed = d * 8 + 3 * d * 32 * 2
    b, f = mla_counts.moe_decode(m, 2, 3, assignments=5, touched=4)
    assert b == 2 * (2 * 2 * fixed + 4 * 3 * d * 32)
    assert f == 2 * 3 * 2 * fixed + 2 * 5 * 3 * d * 32
    b, f = mla_counts.decode_step(m, 2, [5, 7], 5, 4)
    other = 3 * d * 128 + 2 * 3 * d + d + d * 256
    mb, mf = mla_counts.mla_decode(m, 2, [5, 7])
    eb, ef = mla_counts.moe_decode(m, 2, 2, 5, 4)
    assert b == mb + eb + 2 * (2 * other + 2 * d)
    assert f == mf + ef + 2 * 2 * (3 * d * 128 + d * 256)
    # at the cell's widths: 576 latent lanes, 1152 B a token a layer
    v2 = json.loads((bench.ROOT / "chipbench" / "configs"
                     / "deepseek-v2-ep16-8l.json").read_text())
    assert mla_counts.latent_row_bytes(v2) == 1152
    assert 2 * mla_counts.expert_params(v2) == 47185920


def test_readers_on_hand_made_readings():
    root = bench.ROOT
    r = {"device.scope._decode_segment.mla": 0.5,
         "device.scope._decode_segment.moe": 0.25,
         "traced.decode_iterations": 100,
         "traced.mla_bytes": 819e9 * 0.1, "traced.mla_flops": 197e12 * 0.2,
         "traced.moe_bytes": 819e9 * 0.05, "traced.moe_flops": 1.0,
         "peak.hbm_bytes_per_s": 819e9, "peak.bf16_flops_per_s": 197e12}
    read = {n: bench.load_reader(root, n) for n in (
        "mla_decode_ms.mla", "mla_decode_roofline.mla", "moe_decode_ms.mla",
        "moe_decode_roofline.mla")}
    assert read["mla_decode_ms.mla"](r) == pytest.approx(5.0)
    assert read["moe_decode_ms.mla"](r) == pytest.approx(2.5)
    # the larger of the two bounds: FLOPs for mla, bytes for moe
    assert read["mla_decode_roofline.mla"](r) == pytest.approx(40.0)
    assert read["moe_decode_roofline.mla"](r) == pytest.approx(20.0)
    for f in read.values():
        assert f({}) is None


def test_mix_is_the_serving_generator_s():
    """The cell's mix is the serving generator's, under its own kind: the
    same lengths and sampled share as ``traffic.generate``, in one order
    for every seed (the seed draws the tokens and which requests sample),
    deterministic per seed, clipped, contexts within the engine's 8,192,
    and the slots started part-way at the same fractions for every
    seed."""
    path = bench.ROOT / "chipbench" / "traffic" / "mla-decode-8k.json"
    mix = json.loads(path.read_text())
    assert mix["kind"] == "serve_moe"
    with pytest.raises(ValueError, match="not a serving mix"):
        traffic.generate(mix, 1, 100)
    plain = dict(mix, kind="serve")
    a = serve_moe.pool(plain, 2 ** 33 + 17, 12800)
    assert a == serve_moe.pool(plain, 2 ** 33 + 17, 12800)
    b = serve_moe.pool(plain, 5, 12800)
    g = traffic.generate(plain, 2 ** 33 + 17, 12800)

    def lengths(reqs):
        return [(len(r.prompt), r.max_new) for r in reqs]

    assert lengths(a) == lengths(b)
    assert sorted(lengths(a)) != lengths(a)
    assert (sorted(len(r.prompt) for r in a)
            == sorted(len(r.prompt) for r in g))
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in g)
    sampled = [r.temperature > 0 for r in a]
    assert sum(sampled) == sum(r.temperature > 0 for r in g) == 86
    assert sampled != [r.temperature > 0 for r in b]
    assert a[0].prompt != b[0].prompt
    assert all(512 <= len(r.prompt) <= 6144 and 1024 <= r.max_new <= 2048
               for r in a)
    mid = serve_moe.mid_request(a, 32, 2 ** 33 + 17, 12800)
    assert mid == serve_moe.mid_request(a, 32, 2 ** 33 + 17, 12800)
    assert lengths(mid) == lengths(serve_moe.mid_request(b, 32, 5, 12800))
    assert mid[32:] == a[32:]
    assert all(len(m.prompt) + m.max_new == len(r.prompt) + r.max_new
               and m.prompt[:len(r.prompt)] == r.prompt
               for m, r in zip(mid, a))
    assert max(len(r.prompt) + r.max_new for r in mid) <= 8192


def test_golden_ranks_spread_every_run():
    """Any run of consecutive requests spreads over the quantile grid: in
    every run of 8 of 256, each eighth of the grid holds at most 2."""
    r = serve_moe.golden_ranks(256)
    assert sorted(r) == list(range(256))
    for i in range(256 - 8):
        assert np.bincount(r[i:i + 8] // 32, minlength=8).max() <= 2


def _run(checkout, seed, seconds=2.0, trace=0):
    c = bench.load_cell(checkout, "tiny-v2.moe")
    args = bench.parse_args(["--workload", "tiny-v2.moe", "--seed",
                             str(seed), "--seconds", str(seconds),
                             "--trace", str(trace)])
    return serve_moe.run(c, args, jax.devices(), bench.CompileClock(), 0.0)


def test_sound_run_is_correct(checkout):
    result, checks = _run(checkout, 2 ** 33 + 5)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(checks) == ["logit_gap_mean", "tokens_compared",
                            "failed_requests"]


def test_control_lies_past_the_limit(checkout):
    c = bench.load_cell(checkout, "tiny-v2.moe")
    for seed in (5, 6):
        args = bench.parse_args(["--workload", "tiny-v2.moe", "--seed",
                                 str(seed), "--seconds", "1.5"])
        r = serve_moe.limit_readings(c, args, jax.devices(),
                                     bench.CompileClock(), control=True)
        assert bench.passes(r["program"]), r
        assert not bench.passes(r["control"]), r
        v = r["values"]
        assert 0.0 <= v["near_tie_share"] <= 1.0
        for side in ("program", "bf16", "control"):
            assert 0.0 <= v[f"{side}.off_best"] <= 1.0
            assert v[f"{side}.gap_mean"] <= v[f"{side}.gap"]
        assert v["control.off_best"] > v["program.off_best"]
