"""Continuous batching (paged KV + while-loop decode) vs the PR-5 static
``generate`` loop.

Workload: a seeded synthetic request trace with ragged prompt lengths and
generation budgets (``synth_trace`` — also the source of the committed CI
replay fixture ``tests/data/serve_trace.json``).  Two ways to serve it:

  * **engine** — ``serve.Engine``: requests stream through ``num_slots``
    decode slots; finished requests retire mid-flight and waiting ones
    take their slots, so short requests never wait for the batch's
    straggler.
  * **static baseline** — the pre-engine ``generate_loop``: requests are
    grouped into fixed batches of ``num_slots`` in arrival order, prompts
    right-padded to the batch max, and every batch decodes until its
    *longest* budget is exhausted — the convoy effect continuous batching
    exists to kill.

Tokens/sec counts only *requested* tokens (the baseline's overrun tokens
are waste, not throughput).  The report (``BENCH_serve.json``) carries the
engine's per-step tokens/sec trajectory, per-request TTFT / per-token
latency histograms, the engine's metrics-registry snapshot, and the per-step
queue-depth / occupancy / preemption series read back from the flight
recorder (``docs/observability.md``).  The timed engine run also exports a
Chrome trace (``BENCH_serve_trace.json``) from the engine's tracing spans —
``python -m repro.obs.trace --validate`` gates it in ``scripts/check.sh``.
``--smoke`` runs a reduced model and also gates engine-vs-loop greedy
parity (same tokens on a uniform batch) — the CI hook in
``scripts/check.sh``.

Row format matches the other benchmarks: ``name,usec,extras``.
"""
import argparse
import dataclasses
import json
import os
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json")
FAULTS_JSON_PATH = os.path.join(os.path.dirname(JSON_PATH),
                                "BENCH_serve_faults.json")
TRACE_JSON_PATH = os.path.join(os.path.dirname(JSON_PATH),
                               "BENCH_serve_trace.json")


def synth_trace(seed: int, n: int, vocab: int, *, plen_lo=4, plen_hi=48,
                new_lo=2, new_hi=48):
    """Seeded ragged request trace; deterministic across runs/machines."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n):
        plen = int(rng.integers(plen_lo, plen_hi + 1))
        reqs.append({
            "uid": uid,
            "prompt": rng.integers(0, vocab, plen).tolist(),
            "max_new": int(rng.integers(new_lo, new_hi + 1)),
            "temperature": float(rng.choice([0.0, 0.7, 1.0])),
            "top_k": int(rng.choice([0, 40])),
            "top_p": float(rng.choice([1.0, 0.95])),
        })
    return reqs


def _run_engine(cfg, params, reqs, *, num_slots, max_seq, seed=0,
                segment_len=8, tracer=None):
    from repro.serve import Engine, EngineConfig
    ecfg = EngineConfig(num_slots=num_slots, page_size=16, max_seq=max_seq,
                        segment_len=segment_len, seed=seed)
    # flight capacity sized to hold every step of the run so the per-step
    # queue/occupancy series in the report covers the whole trace
    eng = Engine(cfg, params, ecfg, tracer=tracer, flight_capacity=4096)
    for r in reqs:
        eng.submit(r["prompt"], r["max_new"], temperature=r["temperature"],
                   top_k=r["top_k"], top_p=r["top_p"], uid=r["uid"])
    t0 = time.perf_counter()
    trajectory = []   # (elapsed_s, cumulative_tokens)
    while not eng.idle:
        eng.step()
        trajectory.append((time.perf_counter() - t0, eng.tokens_generated))
    wall = time.perf_counter() - t0
    tokens = eng.tokens_generated
    ttft = [eng.metrics[r["uid"]]["first_token"]
            - eng.metrics[r["uid"]]["submitted"] for r in reqs]
    per_token = []
    for r in reqs:
        ts = eng.metrics[r["uid"]]["token_times"]
        per_token += list(np.diff(ts))
    outs = {r["uid"]: eng.collect(r["uid"]) for r in reqs}
    return wall, tokens, trajectory, ttft, per_token, outs, eng


def _step_series(eng):
    """Per-step queue-depth / occupancy / free-page series from the engine's
    flight recorder — the observability satellite's report columns."""
    num_pages = eng.kv.num_pages
    series = []
    for rec in eng.flight.records():
        free = rec.get("free_pages", num_pages)
        series.append({
            "step": rec.get("step"),
            "queue_depth": rec.get("queue_depth"),
            "running": rec.get("running"),
            "occupancy": round((num_pages - free) / num_pages, 4),
            "tokens_total": rec.get("tokens_total"),
        })
    return series


def _run_static(cfg, params, reqs, *, num_slots, scfg):
    """Arrival-order fixed batches through the legacy loop."""
    import jax.numpy as jnp
    from repro.serve import generate_loop
    t0 = time.perf_counter()
    useful = 0
    for i in range(0, len(reqs), num_slots):
        batch = reqs[i:i + num_slots]
        plen = max(len(r["prompt"]) for r in batch)
        num_new = max(r["max_new"] for r in batch)
        prompts = np.zeros((len(batch), plen), np.int32)
        for j, r in enumerate(batch):
            prompts[j, :len(r["prompt"])] = r["prompt"]
        generate_loop(cfg, params, jnp.asarray(prompts), num_new, scfg=scfg)
        useful += sum(r["max_new"] for r in batch)
    return time.perf_counter() - t0, useful


def _hist(xs, bins=8):
    if not len(xs):
        return {}
    counts, edges = np.histogram(np.asarray(xs) * 1e3, bins=bins)
    return {"unit": "ms", "edges": [float(e) for e in edges],
            "counts": [int(c) for c in counts],
            "p50": float(np.percentile(np.asarray(xs) * 1e3, 50)),
            "p99": float(np.percentile(np.asarray(xs) * 1e3, 99))}


def run(smoke: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import ServeConfig, generate, generate_loop

    rows = []
    cfg = get_config("minicpm_2b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    num_slots = 8
    n_req = 24 if smoke else 64
    reqs = synth_trace(0, n_req, cfg.vocab_size)
    max_seq = max(len(r["prompt"]) + r["max_new"] for r in reqs)
    # static batching pads every request to its batch's max prompt AND max
    # budget, so its sequences run longer than any single request's
    static_max = (max(len(r["prompt"]) for r in reqs)
                  + max(r["max_new"] for r in reqs))
    scfg = ServeConfig(max_seq=static_max, ep_axis=None)
    # the trace carries per-request sampling knobs; the engine honors them,
    # the legacy loop can only sample with one global setting (its dead-knob
    # limitation) — but it must still pay for sampling, so the timed static
    # run uses the trace's modal knobs instead of silently argmaxing
    scfg_time = dataclasses.replace(scfg, greedy=False, temperature=1.0,
                                    top_k=40, top_p=0.95)

    # -- parity gate: engine greedy == legacy loop greedy ------------------
    rng = np.random.default_rng(3)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (num_slots, 8)),
                          jnp.int32)
    want = generate_loop(cfg, params, prompts, 6, scfg=scfg)
    got = generate(cfg, params, prompts, 6, scfg=scfg)
    parity = bool((np.asarray(want) == np.asarray(got)).all())
    assert parity, "engine-greedy output diverged from the legacy loop"
    rows.append(("serve_parity_engine_vs_loop", 0.0,
                 f"batch={num_slots};equal={parity}"))

    # -- throughput: warm both paths once, then time -----------------------
    from repro.obs import trace as obs_trace
    _run_engine(cfg, params, reqs, num_slots=num_slots, max_seq=max_seq)
    tracer = obs_trace.Tracer()   # explicit tracer → exported Chrome trace
    e_wall, e_tok, traj, ttft, per_tok, _, eng = _run_engine(
        cfg, params, reqs, num_slots=num_slots, max_seq=max_seq,
        tracer=tracer)
    _run_static(cfg, params, reqs, num_slots=num_slots, scfg=scfg_time)
    s_wall, s_tok = _run_static(cfg, params, reqs, num_slots=num_slots,
                                scfg=scfg_time)
    assert e_tok == sum(r["max_new"] for r in reqs) == s_tok
    e_tps, s_tps = e_tok / e_wall, s_tok / s_wall
    speedup = e_tps / s_tps
    rows.append((
        f"serve_continuous_vs_static_b{num_slots}",
        e_wall / e_tok * 1e6,
        f"engine_tok_per_s={e_tps:.1f};static_tok_per_s={s_tps:.1f}"
        f";speedup={speedup:.2f};requests={n_req}"
        f";ttft_p50_ms={_hist(ttft)['p50']:.2f}",
    ))

    report = {
        "smoke": smoke,
        "config": "minicpm_2b.reduced",
        "num_slots": num_slots,
        "requests": n_req,
        "trace_seed": 0,
        "requested_tokens": e_tok,
        "engine_tokens_per_sec": e_tps,
        "static_tokens_per_sec": s_tps,
        "speedup": speedup,
        "tokens_per_sec_trajectory": [
            {"t_s": round(t, 4), "tokens": k} for t, k in traj],
        "ttft_hist": _hist(ttft),
        "per_token_hist": _hist(per_tok),
        "parity_engine_vs_loop": parity,
        "registry_snapshot": eng.registry.snapshot(),
        "step_series": _step_series(eng),
    }
    with open(JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)

    # Chrome trace of the timed engine run (chrome://tracing / Perfetto);
    # scripts/check.sh validates its schema via `repro.obs.trace --validate`
    chrome = obs_trace.chrome_trace(tracer.spans(), t0=tracer.t0,
                                    process_name="bench_serve")
    with open(TRACE_JSON_PATH, "w") as f:
        json.dump(chrome, f, indent=1)

    # throughput gate: ragged continuous batching must beat static batching
    # (CI smoke allows a little scheduling noise on shared runners)
    floor = 1.0 if smoke else 1.1
    assert speedup >= floor, (
        f"continuous batching ({e_tps:.1f} tok/s) did not beat the static "
        f"loop ({s_tps:.1f} tok/s) at batch {num_slots}: {speedup:.2f}x "
        f"< {floor}x")
    return rows


def run_faults(smoke: bool = False):
    """Chaos goodput: the same synthetic trace served while a seeded
    ``FaultPlan`` injects page exhaustion, forced preemptions and one NaN
    poisoning, on an *optimistic-admission* engine with an undersized page
    pool.  Gates: the engine drains, only the poisoned request FAILs, every
    other request's tokens are bit-identical to a fault-free reserve-mode
    golden run, no pages leak, and at least one preemption round-tripped.
    Goodput counts only FINISHED requests' requested tokens.  Report:
    ``BENCH_serve_faults.json``."""
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve import Engine, EngineConfig, FaultPlan, RequestStatus

    cfg = get_config("minicpm_2b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    n_req = 16 if smoke else 32
    # new_lo=4 so the poisoned request is guaranteed to reach poison_pos
    reqs = synth_trace(1, n_req, cfg.vocab_size, new_lo=4)
    max_seq = max(len(r["prompt"]) + r["max_new"] for r in reqs)
    num_slots, page_size = 8, 16
    worst = -(-max_seq // page_size)
    # undersized pool: 3 pages/slot vs a worst case of `worst` — admission
    # is a gamble and growth/preemption must carry the slack
    ecfg = EngineConfig(num_slots=num_slots, page_size=page_size,
                        max_seq=max_seq, num_pages=3 * num_slots,
                        segment_len=8, seed=0, admission="optimistic")
    poison_uid = 3
    poison_pos = len(reqs[poison_uid]["prompt"]) + 2
    plan = FaultPlan.random(7, 30, p_exhaust=0.2, p_preempt=0.1,
                            poison=(poison_uid, poison_pos))
    # guarantee preemption coverage regardless of the random draw
    plan = dataclasses.replace(
        plan, preempt_steps=plan.preempt_steps | {2, 4})

    def submit_all(eng):
        for r in reqs:
            eng.submit(r["prompt"], r["max_new"],
                       temperature=r["temperature"], top_k=r["top_k"],
                       top_p=r["top_p"], uid=r["uid"])

    golden_eng = Engine(cfg, params, dataclasses.replace(
        ecfg, admission="reserve", num_pages=None))
    submit_all(golden_eng)
    golden = golden_eng.run()

    eng = Engine(cfg, params, ecfg, faults=plan, flight_capacity=2048)
    submit_all(eng)
    t0 = time.perf_counter()
    steps = 0
    while not eng.idle and steps < 1000:
        eng.step()
        eng.validate()           # invariants hold under every injected fault
        steps += 1
    wall = time.perf_counter() - t0
    assert eng.idle, "chaos engine failed to drain"
    assert eng.kv.free_pages == eng.kv.num_pages, "page leak under faults"
    assert eng.status(poison_uid) == RequestStatus.FAILED
    assert eng.stats["preemptions"] >= 1
    # the NaN poisoning must have tripped the flight recorder's black box
    dump = eng.flight.last_dump
    assert dump is not None and dump["reason"] == "nan_quarantine", (
        "poisoned request did not produce a nan_quarantine flight dump")
    assert poison_uid in dump["context"]["uids"]

    finished = [r for r in reqs
                if eng.status(r["uid"]) == RequestStatus.FINISHED]
    assert len(finished) == n_req - 1, "a healthy request did not finish"
    for r in finished:
        assert eng.collect(r["uid"]) == golden[r["uid"]], (
            f"uid {r['uid']} not bit-identical under faults")
    goodput_tok = sum(r["max_new"] for r in finished)
    goodput = goodput_tok / wall

    statuses = {}
    for r in reqs:
        statuses[eng.status(r["uid"]).value] = \
            statuses.get(eng.status(r["uid"]).value, 0) + 1
    report = {
        "smoke": smoke,
        "config": "minicpm_2b.reduced",
        "requests": n_req,
        "trace_seed": 1,
        "fault_seed": 7,
        "poison": {"uid": poison_uid, "pos": poison_pos},
        "exhaust_steps": sorted(plan.exhaust_steps),
        "preempt_steps": sorted(plan.preempt_steps),
        "steps_to_drain": steps,
        "statuses": statuses,
        "engine_stats": eng.stats,
        "registry_snapshot": eng.registry.snapshot(),
        "step_series": _step_series(eng),
        "flight_dump_reason": dump["reason"],
        "flight_replay_tail": eng.flight.replay(8),
        "goodput_tokens": goodput_tok,
        "goodput_tokens_per_sec": goodput,
        "parity_with_fault_free_golden": True,
        "page_leak": False,
    }
    with open(FAULTS_JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)
    return [(
        "serve_faults_goodput",
        wall / goodput_tok * 1e6,
        f"goodput_tok_per_s={goodput:.1f};preemptions="
        f"{eng.stats['preemptions']};page_grows={eng.stats['page_grows']}"
        f";failed=1;finished={len(finished)};steps={steps}",
    )]


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller trace + relaxed throughput gate")
    ap.add_argument("--faults", action="store_true",
                    help="chaos mode: seeded FaultPlan goodput run only")
    args = ap.parse_args()
    rows = run_faults(smoke=args.smoke) if args.faults else run(
        smoke=args.smoke)
    for r in rows:
        print(",".join(map(str, r)))
