"""Hardened-serving tests: request lifecycle, preemption, fault injection.

Chaos coverage for the robustness layer: seeded ``FaultPlan`` runs (page
exhaustion + NaN poisoning + forced preemption) must drain with correct
per-request terminal statuses, zero page/slot leaks (``Engine.validate()``
after every step), unaffected requests bit-identical to a fault-free run,
and preempted requests resuming bit-identically (the counter-sampler
payoff).  Plus the lifecycle satellites (duplicate-uid rejection, partial
results on non-drain, cancel, virtual-clock deadlines) and the fused-kernel
opt-in XLA fallback (``use_fusion=True`` survives a forced Pallas failure
with ``REPRO_FUSION_FALLBACK=1``).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import fusion
from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm
from repro.serve import (Engine, EngineConfig, EngineDrainError, FaultPlan,
                         NO_FAULTS, PagedKvCache, Request, RequestStatus,
                         Scheduler)
from repro.serve import engine as engine_mod
from repro.serve.faults import POISON_OFF

KEY = jax.random.PRNGKey(0)
_PARAMS = {}


def _model(name="minicpm_2b"):
    if name not in _PARAMS:
        cfg = get_config(name).reduced()
        _PARAMS[name] = (cfg, lm.init_params(cfg, KEY))
    return _PARAMS[name]


# Shared engine shapes — reused so the lru-cached jits compile once.
E_RES = EngineConfig(num_slots=3, page_size=4, max_seq=64, segment_len=4,
                     seed=7)
E_OPT = EngineConfig(num_slots=3, page_size=4, max_seq=64, segment_len=4,
                     seed=7, admission="optimistic", num_pages=10,
                     thrash_preemptions=50)   # watermark effectively off
E_SMALL = EngineConfig(num_slots=1, page_size=4, max_seq=64, num_pages=2,
                       segment_len=4, seed=7)


def _trace(n, seed, vocab):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        plen = int(rng.integers(3, 12))
        out.append(dict(
            prompt=rng.integers(1, vocab, size=plen).tolist(),
            max_new=int(rng.integers(4, 10)),
            temperature=float(rng.choice([0.0, 0.8, 1.0])),
            top_k=int(rng.choice([0, 20])),
            top_p=float(rng.choice([1.0, 0.9]))))
    return out


def _submit_all(eng, reqs):
    for r in reqs:
        eng.submit(r["prompt"], r["max_new"], temperature=r["temperature"],
                   top_k=r["top_k"], top_p=r["top_p"])


_GOLDEN = {}


def _golden(n, seed):
    """Fault-free reserve-mode outputs for _trace(n, seed) — the parity
    reference every chaos run is compared against."""
    if (n, seed) not in _GOLDEN:
        cfg, params = _model()
        eng = Engine(cfg, params, E_RES)
        _submit_all(eng, _trace(n, seed, cfg.vocab_size))
        _GOLDEN[(n, seed)] = eng.run()
    return _GOLDEN[(n, seed)]


# ---------------------------------------------------------------------------
# Page growth + scheduler modes (no model)
# ---------------------------------------------------------------------------

def test_kvcache_grow():
    kv = PagedKvCache(num_slots=2, num_pages=4, page_size=4,
                      max_pages_per_slot=3)
    kv.allocate_pages(0, 1)
    assert kv.capacity(0) == 4
    assert kv.grow(0, 2)
    assert kv.num_owned(0) == 3 and kv.capacity(0) == 12
    assert kv.free_pages == 1
    # table row follows growth
    assert list(kv._table[0][:3]) == kv.slot_pages(0)
    kv.check_invariants()
    assert not kv.grow(0, 1)          # at max_pages_per_slot — all-or-nothing
    assert kv.num_owned(0) == 3
    kv.allocate_pages(1, 1)
    assert not kv.grow(1, 1)          # free list empty
    with pytest.raises(ValueError):
        kv.grow(5)                    # unoccupied slot
    kv.release(0)
    assert kv.free_pages == 3
    kv.check_invariants()


def test_scheduler_optimistic_reserves_prompt_plus_one():
    kv = PagedKvCache(num_slots=2, num_pages=20, page_size=4,
                      max_pages_per_slot=10)
    sched = Scheduler(2, kv, mode="optimistic")
    req = Request(uid=0, prompt=[1] * 9, max_new=20)
    assert sched.required_pages(req) == 4          # ceil(9/4) + 1
    small = Request(uid=1, prompt=[1], max_new=2)
    assert sched.required_pages(small) == 1        # never above worst case
    sched.submit(req)
    sched.admit()
    assert kv.num_owned(0) == 4                    # not the worst-case 8
    sched.check_invariants()
    with pytest.raises(ValueError):
        Scheduler(2, kv, mode="yolo")


def test_scheduler_youngest_and_requeue_front():
    kv = PagedKvCache(num_slots=3, num_pages=30, page_size=4,
                      max_pages_per_slot=10)
    sched = Scheduler(3, kv)
    for uid in range(3):
        sched.submit(Request(uid=uid, prompt=[1, 2], max_new=4))
    sched.admit()
    assert sched.youngest_running() == 2           # admitted last
    victim = sched.preempt(2)
    assert victim.uid == 2
    sched.requeue_front(Request(uid=2, prompt=[1, 2, 3], max_new=3))
    assert sched.waiting[0].uid == 2               # ahead of later arrivals
    sched.check_invariants()
    assert sched.youngest_running() == 1


def test_faultplan_default_is_noop_and_random_is_deterministic():
    assert not NO_FAULTS.active
    assert NO_FAULTS.poison_uid == POISON_OFF
    assert not NO_FAULTS.allocator_exhausted(0)
    assert NO_FAULTS.clock_skew(3) == 0.0
    p1 = FaultPlan.random(5, 100, p_exhaust=0.2, p_preempt=0.1, p_delay=0.1)
    p2 = FaultPlan.random(5, 100, p_exhaust=0.2, p_preempt=0.1, p_delay=0.1)
    assert p1 == p2
    assert p1.active


# ---------------------------------------------------------------------------
# Lifecycle satellites
# ---------------------------------------------------------------------------

def test_submit_rejects_duplicate_uid():
    cfg, params = _model()
    eng = Engine(cfg, params, E_RES)
    eng.submit([1, 2, 3], 2, uid=5)
    with pytest.raises(ValueError, match="duplicate uid 5"):
        eng.submit([4, 5], 2, uid=5)
    eng.run()
    with pytest.raises(ValueError, match="duplicate uid 5"):
        eng.submit([4, 5], 2, uid=5)   # finished uids stay reserved too
    assert eng.submit([4, 5], 2) == 6  # auto-uid continues past manual ones


def test_rejected_submit_leaves_engine_untouched():
    cfg, params = _model()
    eng = Engine(cfg, params, E_RES)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit([1] * 10, eng.ecfg.max_seq, uid=0)
    assert 0 not in eng.metrics and eng._next_uid == 0
    assert eng.submit([1, 2], 2) == 0   # uid 0 was never consumed
    eng.run()


def test_cancel_waiting_and_running():
    cfg, params = _model()
    eng = Engine(cfg, params, E_SMALL)
    u0 = eng.submit([1, 2], 6)          # 5 tokens after step 0 — mid-decode
    u1 = eng.submit([4, 5], 4)
    eng.step()                          # u0 running, u1 waiting
    assert eng.status(u0) == RequestStatus.RUNNING
    assert eng.cancel(u1)               # cancel from the queue
    assert eng.status(u1) == RequestStatus.CANCELLED
    assert eng.cancel(u0)               # cancel mid-decode
    assert eng.status(u0) == RequestStatus.CANCELLED
    assert not eng.cancel(u0)           # already terminal → False
    with pytest.raises(KeyError):
        eng.cancel(99)
    assert eng.idle
    eng.validate()
    assert eng.kv.free_pages == eng.kv.num_pages
    assert len(eng.collect(u0)) > 2     # partial output is collectable
    assert eng.stats["cancellations"] == 2


def test_deadlines_with_virtual_clock():
    cfg, params = _model()
    clock_t = [0.0]
    # latency-spike fault: +10 virtual seconds before step 1
    plan = FaultPlan(delays={1: 10.0})
    eng = Engine(cfg, params, E_SMALL, faults=plan,
                 clock=lambda: clock_t[0])
    u0 = eng.submit([1, 2], 6, deadline=5.0)            # total deadline
    u1 = eng.submit([4, 5], 4, ttft_deadline=2.0)       # queued behind u0
    eng.step()                                          # step 0: u0 admitted
    assert eng.status(u0) == RequestStatus.RUNNING
    eng.step()  # step 1: skew hits +10s → both deadlines blown
    assert eng.status(u0) == RequestStatus.TIMED_OUT    # running → evicted
    assert eng.status(u1) == RequestStatus.TIMED_OUT    # waiting, no TTFT
    assert eng.idle and eng.kv.free_pages == eng.kv.num_pages
    eng.validate()
    assert eng.stats["timeouts"] == 2
    assert len(eng.collect(u0)) > 2     # partial tokens survive the timeout


def test_impossible_head_fails_per_request_not_engine_wide():
    cfg, params = _model()
    eng = Engine(cfg, params, E_SMALL)  # pool: 2 pages of 4 tokens
    big = eng.submit([1] * 20, 10)      # needs 8 pages > pool → hopeless
    small = eng.submit([2, 3], 3)
    res = eng.run()                     # must NOT raise engine-wide
    assert eng.status(big) == RequestStatus.FAILED
    assert eng.status(small) == RequestStatus.FINISHED
    assert 3 <= len(res[small]) <= 5    # may stop early on EOS
    assert eng.stats["failures"] == 1
    eng.validate()


def test_run_attaches_partial_results_on_non_drain():
    cfg, params = _model()
    eng = Engine(cfg, params, E_SMALL)
    u0 = eng.submit([1, 2, 3], 1)       # finishes at prefill, step 0
    u1 = eng.submit([4, 5, 6], 5)
    with pytest.raises(EngineDrainError) as ei:
        eng.run(max_steps=1)
    assert u0 in ei.value.results       # finished work is not lost
    assert u1 not in ei.value.results
    res = eng.run()                     # finish the rest
    assert set(res) == {u0, u1}         # includes earlier-call finishes
    eng.validate()


# ---------------------------------------------------------------------------
# NaN quarantine
# ---------------------------------------------------------------------------

def test_prefill_poison_quarantines_immediately():
    cfg, params = _model()
    reqs = _trace(4, 0, cfg.vocab_size)
    plen = len(reqs[1]["prompt"])
    plan = FaultPlan(poison_uid=1, poison_pos=plen)   # first sampled token
    eng = Engine(cfg, params, E_RES, faults=plan)
    _submit_all(eng, reqs)
    while not eng.idle:
        eng.step()
        eng.validate()
    assert eng.status(1) == RequestStatus.FAILED
    assert eng._out[1] == []            # no token escaped the quarantine
    golden = _golden(4, 0)
    for uid in (0, 2, 3):
        assert eng.collect(uid) == golden[uid]


# ---------------------------------------------------------------------------
# Optimistic admission, preemption, thrash watermark
# ---------------------------------------------------------------------------

def test_optimistic_matches_reserve_and_grows_pages():
    cfg, params = _model()
    eng = Engine(cfg, params, E_OPT)
    _submit_all(eng, _trace(6, 0, cfg.vocab_size))
    while not eng.idle:
        eng.step()
        eng.validate()
    res = {u: eng.collect(u) for u in sorted(eng._terminal)}
    assert res == _golden(6, 0)
    assert eng.stats["page_grows"] > 0  # the optimistic gamble was exercised
    assert eng.kv.free_pages == eng.kv.num_pages


def test_forced_preemption_resumes_bit_identical():
    cfg, params = _model()
    plan = FaultPlan(preempt_steps=frozenset({1, 2}))
    eng = Engine(cfg, params, E_RES, faults=plan)
    reqs = _trace(5, 2, cfg.vocab_size)
    _submit_all(eng, reqs)
    while not eng.idle:
        eng.step()
        eng.validate()
    # PREEMPTED is transient (front-requeued victims re-admit within the
    # same step); the round-trips are surfaced in the per-request metrics.
    assert eng.stats["preemptions"] >= 1
    golden = _golden(5, 2)
    for uid, toks in golden.items():
        assert eng.collect(uid) == toks, f"uid {uid} diverged after resume"
        assert eng.status(uid) == RequestStatus.FINISHED
    preempted = [u for u, m in eng.metrics.items() if m["preemptions"]]
    assert preempted                    # at least one request round-tripped


def test_thrash_watermark_falls_back_to_reserve():
    cfg, params = _model()
    ecfg = dataclasses.replace(E_OPT, thrash_preemptions=3, thrash_window=10)
    plan = FaultPlan(preempt_steps=frozenset({1, 2, 3}))
    eng = Engine(cfg, params, ecfg, faults=plan)
    _submit_all(eng, _trace(6, 0, cfg.vocab_size))
    while not eng.idle:
        eng.step()
        eng.validate()
    assert eng.sched.mode == "reserve"  # watermark tripped
    assert eng.stats["fallback_to_reserve_step"] is not None
    assert {u: eng.collect(u) for u in sorted(eng._terminal)} == _golden(6, 0)


# ---------------------------------------------------------------------------
# Randomized chaos: everything at once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 11])
def test_chaos_plan_drains_with_correct_statuses(seed):
    cfg, params = _model()
    reqs = _trace(8, seed, cfg.vocab_size)
    poison_uid = 2
    poison_pos = len(reqs[poison_uid]["prompt"]) + 2
    plan = FaultPlan.random(seed, 40, p_exhaust=0.25, p_preempt=0.15,
                            p_delay=0.1, delay_s=0.001,
                            poison=(poison_uid, poison_pos))
    eng = Engine(cfg, params, E_OPT, faults=plan)
    _submit_all(eng, reqs)
    steps = 0
    while not eng.idle and steps < 500:
        eng.step()
        eng.validate()
        steps += 1
    assert eng.idle, "chaos engine failed to drain"
    assert eng.kv.free_pages == eng.kv.num_pages, "page leak"
    assert eng.status(poison_uid) == RequestStatus.FAILED
    golden = _golden(8, seed)
    for uid in range(len(reqs)):
        if uid == poison_uid:
            continue
        assert eng.status(uid) == RequestStatus.FINISHED
        assert eng.collect(uid) == golden[uid], \
            f"uid {uid} not bit-identical under faults (seed {seed})"


# ---------------------------------------------------------------------------
# Fused-kernel fallback
# ---------------------------------------------------------------------------

def _fused_output_args(m=32, k=64, n=128, seed=0):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp
    return [jnp.asarray(rng.normal(size=s).astype(np.float32))
            for s in [(m, k), (k, n), (n,), (m, n), (n,), (n,)]]


def test_fallback_matches_xla_reference_exactly(monkeypatch):
    monkeypatch.setenv("REPRO_FUSION_FALLBACK", "1")
    args = _fused_output_args()
    fusion.lowering._COMPILE_CACHE.clear()
    ref = np.asarray(fusion.fused_output_apply(*args, backend="xla",
                                               vjp=False))
    with fusion.force_pallas_failure("fused_output"):
        out = np.asarray(fusion.fused_output_apply(
            *args, backend="pallas_interpret", vjp=False))
        bl = fusion.fallback_blocklist()
        assert "fused_output" in bl and "ForcedPallasFailure" in \
            bl["fused_output"]
        # logged/blocklisted once; later calls keep working via XLA
        out2 = np.asarray(fusion.fused_output_apply(
            *args, backend="pallas_interpret", vjp=False))
    np.testing.assert_array_equal(out, ref)   # the XLA reference, exactly
    np.testing.assert_array_equal(out, out2)
    assert fusion.fallback_blocklist() == {}  # context exit cleans up
    fusion.lowering._COMPILE_CACHE.clear()


def test_fallback_strict_mode_env(monkeypatch):
    # strict is the default: no variable, no fallback
    monkeypatch.delenv("REPRO_FUSION_FALLBACK", raising=False)
    args = _fused_output_args()
    fusion.lowering._COMPILE_CACHE.clear()
    with fusion.force_pallas_failure("fused_output"):
        with pytest.raises(fusion.lowering.ForcedPallasFailure):
            fusion.fused_output_apply(*args, backend="pallas_interpret",
                                      vjp=False)
    fusion.lowering._COMPILE_CACHE.clear()


def test_fused_engine_survives_forced_pallas_failure(monkeypatch):
    """use_fusion=True generation under a Pallas backend that cannot
    compile the fused graphs: with the fallback opted in, every affected
    graph degrades to the XLA reference and the served tokens match the
    healthy fused run."""
    monkeypatch.setenv("REPRO_FUSION_FALLBACK", "1")
    cfg0, params = _model()
    cfg = dataclasses.replace(cfg0, use_fusion=True)
    ecfg = EngineConfig(num_slots=2, page_size=4, max_seq=32, segment_len=4,
                        seed=3)
    reqs = [([3, 1, 4, 1, 5], 4), ([2, 7], 3)]

    def fresh_run():
        engine_mod._jitted_fns.cache_clear()
        fusion.lowering._COMPILE_CACHE.clear()
        eng = Engine(cfg, params, ecfg)
        for p, mn in reqs:
            eng.submit(p, mn)
        return eng.run()

    with ops.use_backend("pallas_interpret"):
        baseline = fresh_run()
        with fusion.force_pallas_failure(
                "fused_output", "fused_gated_mlp_silu", "fused_mlp_gelu",
                "fused_qkv", "fused_attn_out", "fused_attn_out_res"):
            degraded = fresh_run()
            assert fusion.fallback_blocklist(), \
                "no fused graph hit the fallback — forcing missed the model"
    engine_mod._jitted_fns.cache_clear()
    fusion.lowering._COMPILE_CACHE.clear()
    assert degraded == baseline
