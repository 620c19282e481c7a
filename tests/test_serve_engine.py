"""Serving-engine tests: paged KV parity, continuous batching, sampling.

Covers the serving stack bottom-up: the page allocator and scheduler
invariants (property-tested over randomized submit/finish orders), the
counter-based sampler's determinism and knob semantics, paged-vs-dense
logits equivalence across attention families (GQA, sliding-window, MLA,
mamba-mix), the engine against the legacy dense loop, schedule invariance
(results independent of slot count / segment length / backend), the
BatchSpec probe, the prefill/decode tune split, and the committed request
trace replayed end-to-end against pinned outputs.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm
from repro.serve import (Engine, EngineConfig, PagedKvCache, Request,
                         RequestStatus, Scheduler, ServeConfig, generate,
                         generate_loop)
from repro.serve.kvcache import pages_needed
from repro.serve.probe import BatchSpec, max_feasible_slots, trial
from repro.serve.sampling import sample_tokens

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "benchmarks"))
from bench_serve import synth_trace  # noqa: E402

KEY = jax.random.PRNGKey(0)
TRACE_PATH = pathlib.Path(__file__).resolve().parent / "data" / \
    "serve_trace.json"

_PARAMS = {}


def _model(name):
    """Reduced config + params, cached across tests in this module."""
    if name not in _PARAMS:
        cfg = get_config(name).reduced()
        _PARAMS[name] = (cfg, lm.init_params(cfg, KEY))
    return _PARAMS[name]


# --------------------------------------------------------------------------
# Page allocator
# --------------------------------------------------------------------------

def test_pages_needed():
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    assert pages_needed(0, 16) == 1   # a slot always holds >= 1 page


def test_allocator_reserve_release():
    kv = PagedKvCache(num_slots=2, num_pages=6, page_size=4,
                      max_pages_per_slot=3)
    assert kv.free_pages == 6 and kv.trash == 6
    pages = kv.allocate(0, 9)         # ceil(9/4) = 3 pages
    assert len(pages) == 3 and kv.free_pages == 3
    row = kv.table()[0]
    assert list(row) == pages         # every entry allocated, no trash
    assert list(kv.table()[1]) == [6, 6, 6]
    kv.check_invariants()
    with pytest.raises(ValueError):
        kv.allocate(0, 1)             # slot already occupied
    kv.allocate(1, 1)
    assert kv.free_pages == 2
    kv.release(0)
    assert kv.free_pages == 5
    assert list(kv.table()[0]) == [6, 6, 6]
    kv.check_invariants()


def test_allocator_all_or_nothing():
    kv = PagedKvCache(num_slots=2, num_pages=3, page_size=4,
                      max_pages_per_slot=3)
    with pytest.raises(ValueError):
        kv.allocate(0, 17)            # 5 pages > max_pages_per_slot
    kv.allocate(0, 12)
    with pytest.raises(ValueError):
        kv.allocate(1, 4)             # out of pages
    assert kv.free_pages == 0         # failed allocation took nothing
    kv.check_invariants()


# --------------------------------------------------------------------------
# Scheduler (property-tested admission/eviction)
# --------------------------------------------------------------------------

def _mk_sched(num_slots=3, num_pages=12, page_size=4, maxp=4):
    kv = PagedKvCache(num_slots, num_pages, page_size, maxp)
    return Scheduler(num_slots, kv)


def test_scheduler_fifo_head_of_line():
    s = _mk_sched(num_slots=1, num_pages=2, maxp=2)
    s.submit(Request(uid=0, prompt=[1] * 5, max_new=3))   # 2 pages
    s.submit(Request(uid=1, prompt=[1], max_new=1))       # 1 page
    assert [(sl, r.uid) for sl, r in s.admit()] == [(0, 0)]
    # uid 1 fits page-wise but no slot is free: head-of-line blocks
    assert s.admit() == []
    s.retire(0)
    assert [(sl, r.uid) for sl, r in s.admit()] == [(0, 1)]
    s.check_invariants()


def test_scheduler_rejects_oversized():
    s = _mk_sched(page_size=4, maxp=2)
    with pytest.raises(ValueError):
        s.submit(Request(uid=0, prompt=[1] * 8, max_new=1))  # 9 > 8 capacity


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(2, 16), st.integers(0, 2 ** 32 - 1))
def test_scheduler_randomized_invariants(num_slots, num_reqs, seed):
    """Random sizes, random finish order: invariants hold at every step,
    admission is FIFO, every request runs exactly once, everything drains."""
    rng = np.random.default_rng(seed)
    maxp = 4
    s = _mk_sched(num_slots=num_slots, num_pages=num_slots * maxp,
                  page_size=4, maxp=maxp)
    for uid in range(num_reqs):
        s.submit(Request(uid=uid, prompt=[1] * int(rng.integers(1, 9)),
                         max_new=int(rng.integers(1, 9))))
    started, finished = [], []
    while not s.idle:
        for slot, req in s.admit():
            started.append(req.uid)
        s.check_invariants()
        running = list(s.running)
        assert running, "requests waiting but none running (deadlock)"
        victim = running[int(rng.integers(len(running)))]
        finished.append(s.retire(victim).uid)
        s.check_invariants()
    assert started == list(range(num_reqs))       # FIFO admission order
    assert sorted(finished) == list(range(num_reqs))
    assert s.kv.free_pages == s.kv.num_pages      # no leaked pages


# --------------------------------------------------------------------------
# Counter-based sampler
# --------------------------------------------------------------------------

def _sample(logits, *, uids, positions, seed=0, temp=1.0, top_k=0,
            top_p=1.0):
    b = logits.shape[0]
    to = lambda v, dt: jnp.full((b,), v, dt) if np.ndim(v) == 0 \
        else jnp.asarray(v, dt)
    return sample_tokens(
        jnp.asarray(logits, jnp.float32),
        uids=to(uids, jnp.uint32), positions=to(positions, jnp.int32),
        seed=jnp.uint32(seed), temperature=to(temp, jnp.float32),
        top_k=to(top_k, jnp.int32), top_p=to(top_p, jnp.float32))


def test_sampler_greedy_and_topk1():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(6, 40))
    want = logits.argmax(-1)
    # temperature <= 0 → argmax
    np.testing.assert_array_equal(
        np.asarray(_sample(logits, uids=np.arange(6), positions=3, temp=0.0)),
        want)
    # top_k = 1 keeps only the best token, any temperature
    np.testing.assert_array_equal(
        np.asarray(_sample(logits, uids=np.arange(6), positions=3, temp=5.0,
                           top_k=1)),
        want)
    # tiny top_p keeps only the best token too (first token always kept)
    np.testing.assert_array_equal(
        np.asarray(_sample(logits, uids=np.arange(6), positions=3, temp=5.0,
                           top_p=1e-9)),
        want)


def test_sampler_deterministic_in_seed_uid_position():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(8, 64))
    a = np.asarray(_sample(logits, uids=np.arange(8), positions=5, seed=3))
    b = np.asarray(_sample(logits, uids=np.arange(8), positions=5, seed=3))
    np.testing.assert_array_equal(a, b)
    # a different seed / position flips at least one draw over 8 rows
    c = np.asarray(_sample(logits, uids=np.arange(8), positions=5, seed=4))
    d = np.asarray(_sample(logits, uids=np.arange(8), positions=6, seed=3))
    assert (a != c).any() and (a != d).any()


def test_sampler_keyed_by_uid_not_slot():
    """Permuting the batch rows permutes the draws: the stream belongs to
    (uid, position), not to the slot index — the schedule-invariance
    primitive."""
    rng = np.random.default_rng(2)
    logits = rng.normal(size=(5, 32))
    uids = np.array([7, 3, 11, 0, 5])
    base = np.asarray(_sample(logits, uids=uids, positions=9, temp=0.8))
    perm = rng.permutation(5)
    shuf = np.asarray(_sample(logits[perm], uids=uids[perm], positions=9,
                              temp=0.8))
    np.testing.assert_array_equal(shuf, base[perm])


def test_sampler_topk_restricts_support():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(4, 50))
    top5 = np.argsort(-logits, axis=-1)[:, :5]
    for seed in range(10):
        toks = np.asarray(_sample(logits, uids=np.arange(4), positions=seed,
                                  temp=2.0, top_k=5, seed=seed))
        for b in range(4):
            assert toks[b] in top5[b]


# --------------------------------------------------------------------------
# Paged vs dense KV cache: identical logits
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["minicpm_2b", "gemma3_12b",
                                  "deepseek_v2_236b", "jamba_1_5_large"])
def test_paged_cache_matches_dense_logits(arch):
    """Bucket-padded paged prefill + vector-position paged decode must
    reproduce the dense-cache logits across attention families (GQA,
    sliding-window ring, MLA latent, mamba mix)."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(0)
    b, p, new, ps = 3, 8, 5, 4
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)

    caches = lm.init_cache(cfg, b, p + new)
    logits, caches = lm.prefill(cfg, params, caches, {"tokens": prompts})
    dense = [logits]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for t in range(new - 1):
        logits, caches = lm.decode_step(cfg, params, caches, tok, p + t)
        dense.append(logits)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

    ppr = pages_needed(p + new, ps)
    num_pages = ppr * b
    pcaches = lm.init_paged_cache(cfg, b, num_pages, ps)
    table = jnp.asarray(
        np.arange(num_pages).reshape(b, ppr).astype(np.int32))
    padded = jnp.concatenate(                 # prefill at a shape bucket
        [prompts, jnp.zeros((b, 16 - p), jnp.int32)], axis=1)
    logit_idx = jnp.full((b,), p - 1, jnp.int32)
    logits, pcaches = lm.prefill(cfg, params, pcaches, {"tokens": padded},
                                 page_table=table, page_size=ps,
                                 logit_index=logit_idx)
    paged = [logits]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((b,), p, jnp.int32)
    for _ in range(new - 1):
        logits, pcaches = lm.decode_step(cfg, params, pcaches, tok, pos,
                                         page_table=table, page_size=ps)
        paged.append(logits)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1

    for t, (d, q) in enumerate(zip(dense, paged)):
        np.testing.assert_allclose(np.asarray(d), np.asarray(q), atol=2e-4,
                                   err_msg=f"{arch} diverged at step {t}")


# --------------------------------------------------------------------------
# Engine vs the legacy dense loop
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["minicpm_2b", "deepseek_v2_236b"])
def test_engine_matches_legacy_generate(arch):
    cfg, params = _model(arch)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 8)), jnp.int32)
    want = generate_loop(cfg, params, prompts, 6)
    got = generate(cfg, params, prompts, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_validates_budget():
    cfg, params = _model("minicpm_2b")
    prompts = jnp.zeros((2, 10), jnp.int32)
    scfg = ServeConfig(max_seq=12, ep_axis=None)
    with pytest.raises(ValueError, match="exceeds"):
        generate(cfg, params, prompts, 3, scfg=scfg)       # 13 > 12
    with pytest.raises(ValueError, match="num_new"):
        generate(cfg, params, prompts, 0, scfg=scfg)
    out = generate(cfg, params, prompts, 2, scfg=scfg)     # exactly max_seq
    assert out.shape == (2, 12)


def test_generate_temperature_knob_is_live():
    """The PR-5 ServeConfig accepted temperature/greedy but ignored them;
    they must change (and reproducibly determine) the output now."""
    cfg, params = _model("minicpm_2b")
    rng = np.random.default_rng(5)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 6)), jnp.int32)
    greedy = generate(cfg, params, prompts, 8)
    hot = ServeConfig(ep_axis=None, greedy=False, temperature=1.5, seed=13)
    sampled = generate(cfg, params, prompts, 8, scfg=hot)
    again = generate(cfg, params, prompts, 8, scfg=hot)
    assert (np.asarray(sampled) != np.asarray(greedy)).any()
    np.testing.assert_array_equal(np.asarray(sampled), np.asarray(again))


# --------------------------------------------------------------------------
# Continuous batching: ragged traffic, schedule + backend invariance
# --------------------------------------------------------------------------

def _submit_ragged(eng, n=5, seed=1, uid0=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 12))
        mnew = int(rng.integers(1, 9))
        prompt = rng.integers(0, 200, plen).tolist()
        uid = eng.submit(prompt, mnew, temperature=0.8 if i % 2 else 0.0,
                         top_k=50, top_p=0.9, uid=uid0 + i)
        reqs.append((uid, prompt, mnew))
    return reqs


def test_engine_ragged_continuous_batching():
    """More requests than slots, ragged lengths/budgets/knobs: every request
    keeps its prompt, gets exactly max_new tokens, and the allocator drains."""
    cfg, params = _model("minicpm_2b")
    eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                           max_seq=40, segment_len=4, seed=7))
    reqs = _submit_ragged(eng, n=5)
    done = eng.run()
    assert set(done) == {u for u, _, _ in reqs}
    for uid, prompt, mnew in reqs:
        assert done[uid][:len(prompt)] == prompt
        assert len(done[uid]) == len(prompt) + mnew
    eng.sched.check_invariants()
    assert eng.kv.free_pages == eng.kv.num_pages
    for uid, _, _ in reqs:
        m = eng.metrics[uid]
        assert m["submitted"] <= m["first_token"] <= m["finished"]


def test_engine_schedule_invariance():
    """Identical per-request outputs no matter the slot count or segment
    length — sampling is keyed on (seed, uid, position), not the schedule."""
    cfg, params = _model("minicpm_2b")
    outs = []
    for num_slots, seg in [(2, 4), (3, 2), (5, 8)]:
        eng = Engine(cfg, params, EngineConfig(
            num_slots=num_slots, page_size=4, max_seq=40, segment_len=seg,
            seed=7))
        _submit_ragged(eng, n=5, uid0=100)
        outs.append(eng.run())
    assert outs[0] == outs[1] == outs[2]


def test_engine_backend_invariance():
    """Same seed → same tokens under the XLA reference kernels and the
    Pallas (interpret) kernels, across different engine shapes."""
    cfg, params = _model("minicpm_2b")
    outs = []
    for backend, slots, seg in [("xla", 2, 3), ("pallas_interpret", 3, 5)]:
        with ops.use_backend(backend):
            eng = Engine(cfg, params, EngineConfig(
                num_slots=slots, page_size=4, max_seq=16, segment_len=seg,
                seed=11))
            for i in range(4):
                eng.submit([1 + i, 2, 3], 3, temperature=0.9, top_k=5,
                           top_p=0.9, uid=i)
            outs.append(eng.run())
    assert outs[0] == outs[1]


def test_engine_programs_keyed_by_backend():
    """An engine runs programs traced under the kernel backend it was built
    with: same config, other backend → other programs; the backend active
    when the engine later runs does not leak into them."""
    cfg, params = _model("minicpm_2b")
    ecfg = EngineConfig(num_slots=2, page_size=4, max_seq=16)
    xla_eng = Engine(cfg, params, ecfg)
    with ops.use_backend("pallas_interpret"):
        pallas_eng = Engine(cfg, params, ecfg)
    assert pallas_eng._prefill is not xla_eng._prefill
    assert Engine(cfg, params, ecfg)._segment is xla_eng._segment
    with ops.use_backend("pallas_interpret"):
        xla_eng.submit([1, 2, 3], 2, uid=0)
        text = xla_eng._prefill.lower(
            params, xla_eng.caches, xla_eng._state,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(xla_eng.kv.table()[:1]),
            jnp.int32(3), jnp.int32(0), jnp.uint32(0), jnp.uint32(0),
            jnp.float32(0), jnp.int32(0), jnp.float32(1), jnp.int32(2),
            jnp.uint32(0), jnp.int32(0)).as_text()
    assert "pallas_call" not in text


def test_engine_eos_stops_early():
    cfg, params = _model("minicpm_2b")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 6).tolist()
    base = Engine(cfg, params, EngineConfig(num_slots=1, page_size=4,
                                            max_seq=32, segment_len=4))
    uid = base.submit(prompt, 12)
    toks = base.run()[uid][len(prompt):]
    eos = toks[3]                       # pretend the 4th token is EOS
    eng = Engine(cfg, params, EngineConfig(num_slots=1, page_size=4,
                                           max_seq=32, segment_len=4,
                                           eos_token=int(eos)))
    uid = eng.submit(prompt, 12)
    got = eng.run()[uid][len(prompt):]
    # generation stops at the FIRST occurrence of eos in the greedy stream
    assert got == toks[:toks.index(eos) + 1]
    assert len(got) < len(toks)


def test_engine_rejects_impossible_request():
    cfg, params = _model("minicpm_2b")
    eng = Engine(cfg, params, EngineConfig(num_slots=2, page_size=4,
                                           max_seq=16))
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(list(range(15)), 5)


# --------------------------------------------------------------------------
# BatchSpec probe
# --------------------------------------------------------------------------

def test_probe_trial_and_binary_search():
    from repro.serve.probe import _abstract_bytes
    cfg, _ = _model("minicpm_2b")
    # pool must cover at least one slot's reservation
    bad = BatchSpec(num_slots=1, num_pages=1, page_size=4, max_seq=32)
    assert not trial(cfg, bad)
    good = BatchSpec(num_slots=2, num_pages=16, page_size=4, max_seq=32)
    assert trial(cfg, good)
    assert trial(cfg, good, execute=True)    # compile-and-run probe

    spec = max_feasible_slots(cfg, page_size=4, max_seq=32, hi=64)
    assert spec.num_slots == 64              # no budget → hi wins

    # cache bytes grow linearly in slots: pick a budget that admits exactly 5
    base = _abstract_bytes(
        cfg, BatchSpec(num_slots=1, num_pages=8, page_size=4, max_seq=32))
    per_slot = _abstract_bytes(
        cfg, BatchSpec(num_slots=2, num_pages=16, page_size=4, max_seq=32)
    ) - base
    budget = int((base + 4.5 * per_slot) * 1.25)
    spec = max_feasible_slots(cfg, page_size=4, max_seq=32,
                              budget_bytes=budget, hi=64)
    assert spec.num_slots == 5
    with pytest.raises(ValueError):
        max_feasible_slots(cfg, page_size=4, max_seq=32, budget_bytes=1)


def test_probe_reraises_errors_other_than_out_of_memory(monkeypatch):
    """Only RESOURCE_EXHAUSTED reads as "does not fit"; a compile or
    program error propagates instead of silently shrinking the engine."""
    import repro.serve.probe as probe_mod
    cfg, _ = _model("minicpm_2b")
    good = BatchSpec(num_slots=2, num_pages=16, page_size=4, max_seq=32)

    def broken(*a, **k):
        raise ValueError("lowering refused")

    monkeypatch.setattr(probe_mod.lm, "decode_step", broken)
    with pytest.raises(ValueError, match="lowering refused"):
        trial(cfg, good, execute=True)

    def oom(*a, **k):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    monkeypatch.setattr(probe_mod.lm, "decode_step", oom)
    assert not trial(cfg, good, execute=True)


# --------------------------------------------------------------------------
# Prefill-vs-decode tune split
# --------------------------------------------------------------------------

def test_tune_serving_shapes_split_phases(tmp_path):
    from repro.serve.tuning import tune_serving_shapes
    cfg, _ = _model("minicpm_2b")
    report = tune_serving_shapes(cfg, num_slots=4, prefill_buckets=(32,),
                                 max_candidates=4,
                                 cache_dir=str(tmp_path / "tune"))
    assert set(report) == {"decode", "prefill@32"}
    dec = {r["graph"]: r for r in report["decode"]}
    pre = {r["graph"]: r for r in report["prefill@32"]}
    assert set(dec) == set(pre)
    for name in dec:
        assert dec[name]["m"] == 4 and pre[name]["m"] == 32
        assert dec[name]["spec"] and pre[name]["spec"]


# --------------------------------------------------------------------------
# Committed request-trace replay (CI fixture)
# --------------------------------------------------------------------------

# Float32 reduced model (two layers, contractions of at most 128 terms):
# the prefill logits are O(1) and float32 rounding moves them by ~1e-6;
# a jax/XLA upgrade that reassociates those sums stays far below 1e-4,
# while any change to the model's math moves them by orders more.
TRACE_LOGITS_ATOL = 1e-4


def _trace_pins(cfg, params, reqs, seed):
    """Per traced request: the last-position prefill logits of its prompt
    and the first token the sampler draws from them with the request's
    knobs (uid/position/seed keyed, as the engine draws it).  Regenerate
    the fixture's ``prefill_logits``/``first_tokens`` from this when the
    model's math changes on purpose."""
    logits = []
    for r in reqs:
        lg, _ = lm.prefill(cfg, params, None,
                           {"tokens": jnp.asarray([r["prompt"]], jnp.int32)})
        logits.append(np.asarray(lg[0], np.float32))
    return np.stack(logits), _trace_first_tokens(np.stack(logits), reqs, seed)


def _trace_first_tokens(logits, reqs, seed):
    toks = sample_tokens(
        jnp.asarray(logits, jnp.float32),
        uids=jnp.asarray([r["uid"] for r in reqs], jnp.uint32),
        positions=jnp.asarray([len(r["prompt"]) for r in reqs], jnp.int32),
        seed=jnp.uint32(seed),
        temperature=jnp.asarray([r["temperature"] for r in reqs],
                                jnp.float32),
        top_k=jnp.asarray([r["top_k"] for r in reqs], jnp.int32),
        top_p=jnp.asarray([r["top_p"] for r in reqs], jnp.float32))
    return [int(t) for t in toks]


def test_serve_trace_replay_fixture():
    """The committed trace must regenerate bit-identically from its seed;
    the model's prefill logits for every traced prompt must match the
    pinned ones within ``TRACE_LOGITS_ATOL``; the sampler, fed the pinned
    logits, must draw the pinned first tokens (determinism on fixed
    logits); and replaying the trace through the engine must finish every
    request with its whole budget, identically on a second replay — a
    cross-commit guard that does not pin tokens sampled from random
    weights, which flip on rounding."""
    fix = json.loads(TRACE_PATH.read_text())
    cfg, params = _model(fix["config"])
    reqs = synth_trace(fix["trace_seed"], fix["num_requests"],
                       cfg.vocab_size)
    assert reqs == fix["requests"], \
        "synth_trace drifted from the committed fixture"
    seed = fix["engine"]["seed"]
    pinned = np.asarray(fix["prefill_logits"], np.float32)
    logits, _ = _trace_pins(cfg, params, reqs, seed)
    np.testing.assert_allclose(logits, pinned, rtol=0,
                               atol=TRACE_LOGITS_ATOL)
    assert _trace_first_tokens(pinned, reqs, seed) == fix["first_tokens"]

    def replay():
        eng = Engine(cfg, params, EngineConfig(**fix["engine"]))
        for r in reqs:
            eng.submit(r["prompt"], r["max_new"],
                       temperature=r["temperature"], top_k=r["top_k"],
                       top_p=r["top_p"], uid=r["uid"])
        return eng, eng.run()

    eng, done = replay()
    for r in reqs:
        assert eng.status(r["uid"]) == RequestStatus.FINISHED
        toks = done[r["uid"]]
        assert toks[:len(r["prompt"])] == r["prompt"]
        assert len(toks) == len(r["prompt"]) + r["max_new"]
        assert all(0 <= t < cfg.vocab_size for t in toks)
    assert replay()[1] == done
