"""Observability layer tests: tracer spans and their copies in the
``jax.profiler`` trace, engine phase spans and decode counters, named scopes
in the compiled decode segment, metrics registry + kill switch,
kernel-profiler drift goldens (scripted clock), flight-recorder fault
dumps (chaos + drain + validate), tunecache counters, and the
``Engine.stats`` preemption-skew regression.

The engine-backed tests reuse the shapes of ``test_serve_faults`` so the
lru-cached jitted step functions compile once per session.
"""
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import metrics as obs_metrics
from repro.obs import profiler as obs_profiler
from repro.obs.metrics import (METRIC_CATALOG, NULL_REGISTRY, NullRegistry,
                               Registry, default_registry,
                               set_default_registry)
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import NULL_TRACER, Tracer, get_tracer, set_tracer


class FakeClock:
    """Deterministic monotonic clock: each call advances by ``tick``."""

    def __init__(self, tick=1.0, t=0.0):
        self.tick, self.t = tick, t

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_fake_clock():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer", cat="t", a=1) as sp:
        with tr.span("inner", cat="t"):
            pass
        sp.set(b=2)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.parent == outer.sid
    assert outer.parent is None
    assert outer.args == {"a": 1, "b": 2}
    # fake clock: every open/close consumed exactly one tick
    assert outer.duration == pytest.approx(3.0)
    assert inner.duration == pytest.approx(1.0)
    tr.event("mark", cat="t")
    ev = tr.spans()[-1]
    assert ev.start == ev.end


def test_tracer_threads_get_distinct_tids():
    tr = Tracer(clock=FakeClock())
    barrier = threading.Barrier(2)   # both workers alive at once, so the OS
                                     # cannot reuse one thread ident for both
    def work():
        with tr.span("w"):
            barrier.wait(timeout=10)
    ts = [threading.Thread(target=work) for _ in range(2)]
    with tr.span("main"):
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    tids = {s.tid for s in tr.spans()}
    assert len(tids) == 3
    # cross-thread spans never inherit the main thread's parent stack
    assert all(s.parent is None for s in tr.spans())


def test_tracer_bounded_and_clear():
    tr = Tracer(clock=FakeClock(), max_spans=2)
    for i in range(5):
        tr.event(f"e{i}")
    assert len(tr.spans()) == 2 and tr.dropped == 3
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def _profiled(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler.trace`` on the CPU; → the host
    plane's events as {name: [event]}."""
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e)
    return events


def test_span_is_a_profiler_host_event(tmp_path):
    tr = Tracer()

    def spans():
        with tr.span("engine.step", step=3) as sp:
            with tr.span("engine.wait"):
                pass
            sp.set(terminal=1)
        tr.event("engine.preempt", uid=2)

    events = _profiled(tmp_path, spans)
    step, = events["engine.step"]
    wait, = events["engine.wait"]
    assert dict(step.stats)["step"] == 3
    assert dict(step.stats)["terminal"] == 1
    assert step.start_ns <= wait.start_ns
    assert wait.start_ns + wait.duration_ns <= \
        step.start_ns + step.duration_ns
    assert "engine.preempt" in events
    # the in-memory copy is unchanged
    assert [s.name for s in tr.spans()] == ["engine.wait", "engine.step",
                                            "engine.preempt"]
    assert tr.spans()[1].args == {"step": 3, "terminal": 1}


def test_span_without_profiler_records_nothing_there(tmp_path):
    from jax.profiler import TraceAnnotation
    tr = Tracer()
    assert not TraceAnnotation.is_enabled()
    with tr.span("engine.before", uid=1):
        pass

    def during():
        with tr.span("engine.during"):
            pass

    events = _profiled(tmp_path, during)
    assert "engine.during" in events and "engine.before" not in events
    assert [s.name for s in tr.spans()] == ["engine.before",
                                            "engine.during"]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_instruments_and_snapshot():
    reg = Registry()
    reg.counter("serve.tokens").inc(3)
    reg.counter("serve.tokens").inc()
    reg.gauge("serve.queue_depth").set(7)
    h = reg.histogram("serve.step_s")
    for v in (0.01, 0.02, 0.03):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["serve.tokens"] == 4
    assert snap["serve.queue_depth"] == 7.0
    assert snap["serve.step_s"]["count"] == 3
    json.dumps(snap)
    assert reg.enabled
    with pytest.raises(TypeError):
        reg.gauge("serve.tokens")            # name already bound to a counter


def test_histogram_quantile():
    h = obs_metrics.Histogram("x")
    for v in [0.001] * 90 + [1.0] * 10:
        h.observe(v)
    assert h.quantile(0.5) <= 0.01
    assert h.quantile(0.99) >= 0.5


def test_null_registry_is_inert():
    assert not NULL_REGISTRY.enabled
    NULL_REGISTRY.counter("anything").inc(5)
    assert NULL_REGISTRY.counter("anything").value == 0
    assert NULL_REGISTRY.snapshot() == {}


def test_kill_switch_reevaluation(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not obs.enabled()
    old_reg = set_default_registry(None)       # force lazy re-evaluation
    old_tr = set_tracer(None)
    try:
        assert default_registry() is NULL_REGISTRY
        assert get_tracer() is NULL_TRACER
        monkeypatch.setenv("REPRO_OBS", "1")
        set_default_registry(None)
        set_tracer(None)
        assert isinstance(default_registry(), Registry)
        assert isinstance(get_tracer(), Tracer)
    finally:
        set_default_registry(old_reg)
        set_tracer(old_tr)


def test_metric_catalog_covers_every_emitted_name():
    """Append-only contract: every metric name instrumented anywhere in the
    source tree must be declared in METRIC_CATALOG."""
    import pathlib
    import re
    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    pat = re.compile(r"\b(?:counter|gauge|histogram)\(\s*['\"]([a-z0-9_.]+)")
    used = set()
    for path in root.rglob("*.py"):
        used |= set(pat.findall(path.read_text()))
    missing = used - set(METRIC_CATALOG)
    assert not missing, f"metric names missing from METRIC_CATALOG: {missing}"


def test_null_backend_overhead_smoke():
    """The disabled path must be cheap: a million no-op instrument hits in
    well under the generous bound (guards against accidentally putting work
    on the null path)."""
    c = NULL_REGISTRY.counter("serve.tokens")
    t0 = time.perf_counter()
    for _ in range(200_000):
        c.inc()
        with NULL_TRACER.span("s"):
            pass
    assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# Kernel profiler: drift goldens on a scripted clock
# ---------------------------------------------------------------------------

def test_time_callable_median_with_fake_clock():
    clock = FakeClock(tick=1.0)
    med, samples = obs_profiler.time_callable(
        lambda: 0, iters=3, warmup=1, clock=clock)
    # each timed call consumes exactly two ticks (t0 read + t1 read)
    assert samples == [1.0, 1.0, 1.0] and med == 1.0
    with pytest.raises(ValueError):
        obs_profiler.time_callable(lambda: 0, iters=0)


def _rec(name, predicted, measured):
    return obs_profiler.ProfileRecord(
        name=name, shape=(8, 8, 8), backend="xla", spec="bca",
        predicted_s=predicted, measured_s=measured, bound="compute",
        iters=1, warmup=0, samples=[measured])


def test_drift_flags_relative_to_median():
    # constant 100x host-vs-model offset → nothing flagged
    uniform = [_rec(f"g{i}", 1e-6, 1e-4) for i in range(3)]
    assert obs_profiler.drift_flags(uniform) == [False, False, False]
    # one schedule mispriced relative to its peers → only it is flagged
    recs = uniform + [_rec("outlier", 1e-6, 1e-2)]
    assert obs_profiler.drift_flags(recs) == [False, False, False, True]
    table = obs_profiler.attribution_table(recs)
    assert "DRIFT" in table and "outlier" in table
    assert table.count("DRIFT") == 1


def test_profile_graph_smoke():
    from repro import fusion
    g = fusion.fused_mlp_graph("gelu")
    rec = obs_profiler.profile_graph(g, 32, 64, 64, backend="xla",
                                     iters=2, warmup=1)
    assert rec.measured_s > 0 and rec.predicted_s > 0
    assert rec.bound in ("compute", "memory", "collective")
    assert rec.shape == (32, 64, 64)
    json.dumps(rec.to_dict())


def test_make_measure_fn_feeds_autotune():
    from repro import fusion
    g = fusion.fused_mlp_graph("gelu")
    results = fusion.measured_autotune_graph(
        g, 32, 64, 64, backend="xla", max_candidates=4, top_k=2,
        use_cache=False, measure_iters=1, measure_warmup=0)
    assert results and results[0].measured_s is not None


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_replay():
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.record(step=i, events=[("admit", {"uid": i})], queue_depth=5 - i,
                  running=1, free_pages=2, tokens_total=i)
    recs = fr.records()
    assert [r["step"] for r in recs] == [2, 3, 4]     # oldest two evicted
    assert fr.steps_recorded == 5
    lines = fr.replay(2)
    assert len(lines) == 2
    assert "admit(uid=4)" in lines[-1] and "queue=1" in lines[-1]
    fr.clear()
    assert fr.records() == [] and fr.steps_recorded == 0
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_flight_dump_writes_file(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DUMP_DIR", str(tmp_path))
    fr = FlightRecorder(capacity=4)
    fr.record(step=0, events=[], queue_depth=1, running=0, free_pages=4,
              tokens_total=0)
    dump = fr.dump_on_fault("unit_test", detail="x")
    assert fr.last_dump is dump
    assert dump["reason"] == "unit_test"
    assert dump["context"] == {"detail": "x"}
    assert len(dump["records"]) == 1
    on_disk = json.loads(open(dump["path"]).read())
    assert on_disk["reason"] == "unit_test"


# ---------------------------------------------------------------------------
# Engine integration: dumps under chaos, stats skew, scheduler snapshot
# ---------------------------------------------------------------------------

def _engine(**over):
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Engine, EngineConfig
    cfg = get_config("minicpm_2b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=3, page_size=4, max_seq=64, segment_len=4,
                        seed=7)
    return Engine(cfg, params, ecfg, **over)


@pytest.mark.slow
def test_engine_chaos_dump_and_stats_skew():
    from repro.serve import FaultPlan, RequestStatus
    plan = FaultPlan(preempt_steps=frozenset({1, 3}), poison_uid=1,
                     poison_pos=5)
    tracer = Tracer()
    eng = _engine(faults=plan, tracer=tracer)
    rng = np.random.default_rng(0)
    for uid in range(5):
        eng.submit(rng.integers(1, 50, size=4).tolist(), 6, uid=uid)
    preempted_seen = False
    steps = 0
    while not eng.idle and steps < 200:
        eng.step()
        steps += 1
        st = eng.stats
        # regression: a preempted request is waiting, not in flight — the
        # live view must always agree with the scheduler
        assert st["in_flight"] == len(eng.sched.running)
        assert st["waiting"] == eng.sched.num_waiting
        snap = eng.sched.snapshot()
        assert len(snap["running"]) == st["in_flight"]
        assert len(snap["waiting_uids"]) == st["waiting"]
        if st["preemptions"] and st["waiting"]:
            preempted_seen = True
    assert eng.idle and steps > 1
    assert eng.stats["preemptions"] >= 2
    assert preempted_seen, "never observed a preempted request in the queue"
    # the poisoned request tripped the NaN-quarantine black box
    assert eng.status(1) == RequestStatus.FAILED
    dump = eng.flight.last_dump
    assert dump is not None and dump["reason"] == "nan_quarantine"
    assert 1 in dump["context"]["uids"]
    assert dump["records"], "dump carried no step records"
    assert eng.registry.snapshot()["serve.flight_dumps"] >= 1
    # spans made it to the engine's tracer, every one closed
    names = {s.name for s in tracer.spans()}
    assert "engine.step" in names and "engine.prefill" in names
    assert all(s.end is not None and s.end >= s.start
               for s in tracer.spans())
    # drained engine: corrupting host state must dump on validate()
    eng._done[0] = True
    with pytest.raises(AssertionError):
        eng.validate()
    assert eng.flight.last_dump["reason"] == "validate_failure"
    eng._done[0] = False


@pytest.mark.slow
def test_engine_drain_error_carries_flight_dump():
    from repro.serve import EngineDrainError
    eng = _engine()
    eng.submit([1, 2, 3], 8, uid=0)
    with pytest.raises(EngineDrainError) as ei:
        eng.run(max_steps=1)
    dump = ei.value.flight
    assert dump["reason"] == "engine_drain"
    assert dump["context"]["max_steps"] == 1
    assert eng.flight.last_dump is dump
    eng.run()                                  # drains cleanly afterwards


@pytest.mark.slow
def test_engine_obs_disabled_still_serves(monkeypatch):
    """REPRO_OBS=0: engine runs on the null backend — stats read zeros but
    serving, token accounting, and the flight recorder still work."""
    monkeypatch.setenv("REPRO_OBS", "0")
    old_tr = set_tracer(None)
    try:
        eng = _engine()
        assert isinstance(eng.registry, NullRegistry)
        eng.submit([1, 2, 3, 4], 5, uid=0)
        out = eng.run()
        assert len(out[0]) == 4 + 5            # prompt + generated
        assert eng.tokens_generated == 5       # plain-int path, not gated
        assert eng.stats["preemptions"] == 0
        assert eng.flight.steps_recorded > 0   # black box is never gated
    finally:
        set_tracer(old_tr)


def test_engine_step_spans_nest():
    """One step that admits, decodes and retires: engine.step ⊃
    engine.admit ⊃ engine.prefill ⊃ engine.wait, then engine.grow,
    engine.decode_segment ⊃ engine.wait, engine.harvest, engine.retire;
    engine.prefill opens before the prefill program is dispatched."""
    tracer = Tracer()
    eng = _engine(tracer=tracer)
    dispatched = []
    prefill = eng._prefill

    def timed_prefill(*args):
        dispatched.append(time.perf_counter())
        return prefill(*args)

    eng._prefill = timed_prefill
    eng.submit([1, 2, 3, 4], 3, uid=0)
    eng.step()
    spans = tracer.spans()
    by_sid = {s.sid: s for s in spans}

    def parent(s):
        return by_sid[s.parent].name if s.parent is not None else None

    step, = [s for s in spans if s.name == "engine.step"]
    admit, = [s for s in spans if s.name == "engine.admit"]
    pre, = [s for s in spans if s.name == "engine.prefill"]
    grow, = [s for s in spans if s.name == "engine.grow"]
    seg, = [s for s in spans if s.name == "engine.decode_segment"]
    harvest, = [s for s in spans if s.name == "engine.harvest"]
    retire, = [s for s in spans if s.name == "engine.retire"]
    waits = [s for s in spans if s.name == "engine.wait"]
    assert parent(step) is None
    assert parent(admit) == parent(grow) == parent(seg) == \
        parent(harvest) == parent(retire) == "engine.step"
    assert parent(pre) == "engine.admit"
    assert sorted(parent(w) for w in waits) == ["engine.decode_segment",
                                                "engine.prefill"]
    assert admit.end <= grow.start <= seg.start
    assert seg.end <= harvest.start <= harvest.end <= retire.start
    assert pre.start <= dispatched[0] <= pre.end
    assert admit.args == {"waiting": 1, "admitted": 1}
    assert pre.args["uid"] == 0
    assert seg.args["tokens"] == harvest.args["tokens"] == 2
    assert retire.args == {"slots": 1}
    assert step.args == {"step": 0, "terminal": 1}


def test_decode_counters_match_harvested_tokens():
    """The segment's own counts — while_loop iterations and active slots
    summed over them — equal what the harvested tokens say, step by step,
    with refills cutting segments short."""
    eng = _engine()
    rng = np.random.default_rng(3)
    for uid in range(7):
        eng.submit(rng.integers(1, 50, size=3 + uid).tolist(),
                   [2, 9, 5, 3, 7, 4, 6][uid], uid=uid)
    cut_short = 0
    while not eng.idle:
        before = {r.uid: len(eng.metrics[r.uid]["token_times"])
                  for r in eng.sched.running.values()}
        it0, si0 = eng.decode_iterations, eng.decode_slot_iterations
        newly = eng.step()
        per_slot = []
        for uid in set(before) | set(newly) | {
                r.uid for r in eng.sched.running.values()}:
            n_after = len(eng.metrics[uid]["token_times"])
            # a request admitted in this step brought its prefill token
            n_before = before.get(uid, 1 if n_after else 0)
            per_slot.append(n_after - n_before)
        iters = eng.decode_iterations - it0
        assert iters == max(per_slot, default=0)
        assert eng.decode_slot_iterations - si0 == sum(per_slot)
        if 0 < iters < eng.ecfg.segment_len and eng.sched.num_waiting:
            cut_short += 1
    assert cut_short
    snap = eng.registry.snapshot()
    assert snap["serve.decode_iterations"] == eng.decode_iterations
    assert snap["serve.decode_slot_iterations"] == \
        eng.decode_slot_iterations == eng.tokens_generated - 7


def test_decode_segment_carries_named_scopes():
    import re

    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.serve import engine as engine_mod
    eng = _engine()
    _, segment = engine_mod._jitted_fns(eng.cfg, eng.ecfg,
                                        ops.current_backend())
    text = segment.lower(
        eng.params, eng.caches, eng._state, eng._table_dev, eng._seed,
        jnp.bool_(False), eng._poison_uid, eng._poison_pos).compile().as_text()
    scopes = {c for path in re.findall(r'op_name="([^"]*)"', text)
              for c in re.split(r"[/;]", path)}
    assert {"ops.paged_decode_attention", "attention", "mlp", "lm_head",
            "sample"} <= scopes


def test_kvcache_occupancy_and_scheduler_snapshot():
    from repro.serve import PagedKvCache, Request, Scheduler
    kv = PagedKvCache(num_slots=2, num_pages=8, page_size=4,
                      max_pages_per_slot=4)
    assert kv.used_pages == 0 and kv.occupancy == 0.0
    kv.allocate_pages(0, 2)
    assert kv.used_pages == 2 and kv.occupancy == pytest.approx(0.25)
    sched = Scheduler(2, kv)
    sched.submit(Request(uid=5, prompt=[1, 2], max_new=3))
    snap = sched.snapshot()
    assert snap["waiting_uids"] == [5]
    assert snap["running"] == {}
    assert snap["free_pages"] == kv.free_pages
    json.dumps(snap)


# ---------------------------------------------------------------------------
# Tunecache counters
# ---------------------------------------------------------------------------

def test_tunecache_counters(tmp_path):
    from repro.core.tunecache import TuneCache
    reg = Registry()
    old = set_default_registry(reg)
    try:
        tc = TuneCache(tmp_path)
        key = "k" * 64
        assert tc.lookup(key) is None
        assert reg.counter("tune.cache.misses").value == 1
        tc.store(key, {"specs": ["bca"]})
        assert tc.lookup(key)["specs"] == ["bca"]
        assert reg.counter("tune.cache.hits").value == 1
        # corrupt entry → recovered (deleted) and counted
        tc._file(key).write_text("{not json")
        assert tc.lookup(key) is None
        assert reg.counter("tune.cache.corrupt_recoveries").value == 1
        assert reg.counter("tune.cache.misses").value == 2
        assert not tc._file(key).exists()
    finally:
        set_default_registry(old)
