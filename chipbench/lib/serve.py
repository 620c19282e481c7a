"""Serving cells: the engine under closed-loop or open-loop (Poisson) load,
its end-to-end metrics, the readings its per-layer metrics take, and the
comparison with the reference that decides ``correct``.

The timed path is the program's own: ``Engine.submit`` / ``Engine.step``
on the kernel backend the program defaults to, with the engine's shapes
fixed in the configuration file.  Set-up draws the weights on the device,
compiles (or loads from the persistent cache) the prefill bucket of every
prompt the mix can send and the decode segment, by serving one short
request per bucket, and, in a closed loop, fills every slot (where the mix
says ``"start": "mid-request"``, with requests part-way through their
output, as ``traffic.mid_request`` makes them).  Nothing compiles inside
the window.

After the window: the peak of device memory is read, the engine's state
and the weights are freed, and the reference runs over a sample, drawn
from the seed, of the greedy requests that served tokens (the longest
among them; a request still running when the window closed counts with
the tokens it had served): the widest gap by which a served token's
reference logit lies below the reference's best is compared with the
configuration's limit.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from chipbench.lib import bench, counts, reference, traffic, weights
from chipbench.lib import trace as trace_lib

TAIL_WAIT_S = 60.0          # longest wait past the window for first tokens
SAMPLE_TOKENS = 1200        # served tokens the comparison aims to cover
SAMPLE_MAX = 6              # most requests compared


def model_config(spec: dict):
    """The repository's configuration with the file's overrides, checked
    against the sizes the file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(spec["repo_config"]),
                              **spec.get("repo_overrides", {}))
    pairs = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
             "vocab_size": cfg.vocab_size,
             "tie_word_embeddings": cfg.tie_embeddings,
             "rope_theta": cfg.rope_theta,
             "rope_fraction": cfg.rope_fraction}
    bad = {k: (spec[k], v) for k, v in pairs.items() if spec[k] != v}
    if bad:
        raise ValueError(f"{spec['name']}: file and program disagree "
                         f"(file, program): {bad}")
    return cfg


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    traced: bool
    iterations: int
    decode_tokens: int
    contexts: list           # per decode token: keys attended
    prompts: list            # prompt lengths prefilled in the step
    waiting: int             # requests queued when the step ended


class Driver:
    """Drives one engine with one mix and keeps the harness's counts."""

    def __init__(self, eng, reqs, mix, clock=time.perf_counter):
        self.eng, self.reqs, self.mix, self.clock = eng, reqs, mix, clock
        self.next_req = 0
        self.info = {}          # uid → {"req", "due", "submit"}
        self.steps: list[StepRecord] = []
        self.profile = None     # a trace_lib.Profile in a traced run

    # -- load -----------------------------------------------------------
    def submit(self, req, due=None):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.submit"):
            now = self.clock()
            uid = self.eng.submit(list(req.prompt), req.max_new,
                                  temperature=req.temperature,
                                  top_p=req.top_p)
        self.info[uid] = {"req": req, "due": now if due is None else due,
                          "submit": now}
        return uid

    def submit_next(self):
        req = self.reqs[self.next_req % len(self.reqs)]
        self.next_req += 1
        return self.submit(req)

    def step(self):
        from jax.profiler import TraceAnnotation
        eng = self.eng
        before = {}
        for slot, r in eng.sched.running.items():
            before[r.uid] = len(eng.metrics[r.uid]["token_times"])
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            newly = eng.step()
        t1 = self.clock()
        per_slot, contexts, prompts = [], [], []
        for uid, m in self._touched(before, newly):
            n_after = len(m["token_times"])
            plen = len(self.info[uid]["req"].prompt)
            n_before = before.get(uid)
            if n_before is None:        # admitted in this step
                if n_after == 0:
                    continue
                prompts.append(plen)
                n_before = 1
            per_slot.append(n_after - n_before)
            # generated token k (k >= 2) is decoded attending plen + k - 1
            contexts.extend(plen + k - 1 for k in range(n_before + 1,
                                                        n_after + 1))
        self.steps.append(StepRecord(
            t0, t1, self.profile is not None and self.profile.on,
            max(per_slot, default=0),
            sum(per_slot), contexts, prompts, eng.sched.num_waiting))
        return newly

    def _touched(self, before, newly):
        eng = self.eng
        uids = set(before) | set(newly) | {
            r.uid for r in eng.sched.running.values()}
        return [(u, eng.metrics[u]) for u in sorted(uids)]

    # -- set-up -----------------------------------------------------------
    def warm_up(self, ecfg):
        """One short request per prefill bucket the mix can use, served to
        the end: every program of the window is compiled or loaded."""
        from repro.serve.engine import _next_bucket
        buckets = sorted({_next_bucket(len(r.prompt), ecfg.min_bucket,
                                       ecfg.slot_capacity)
                          for r in self.reqs})
        for b in buckets:
            plen = min(b, ecfg.max_seq - 2)
            self.eng.submit([1] * plen, 2)
        self.eng.run()
        return buckets

    # -- windows ----------------------------------------------------------
    def _tick(self, now):
        if self.profile is not None:
            self.profile.tick(now)

    def _stop_profile(self):
        if self.profile is not None:
            self.profile.stop()

    def closed(self, seconds, root=None):
        """The closed loop for ``seconds``; with ``root``, profiled."""
        eng = self.eng
        t_start = self.clock()
        t_end = t_start + seconds
        if root is not None:
            self.profile = trace_lib.Profile(root, t_start, seconds)
        tok0 = eng.tokens_generated
        while True:
            now = self.clock()
            if now >= t_end:
                break
            self._tick(now)
            for uid in self.step():
                self.info[uid]["done"] = self.clock()
                self.submit_next()
        t_stop = self.clock()
        self._stop_profile()
        return {"t_start": t_start, "t_end": t_stop,
                "tokens": eng.tokens_generated - tok0}

    def poisson(self, seconds, root=None):
        """The open loop: ``preroll_s`` of arrivals, then the window of
        ``seconds``; with ``root``, profiled."""
        eng = self.eng
        base = self.clock()
        t_open = base + float(self.mix["preroll_s"])
        t_end = t_open + seconds
        if root is not None:
            self.profile = trace_lib.Profile(root, t_open, seconds)
        due = [base + r.due for r in self.reqs]
        in_window = []
        i = 0
        while True:
            now = self.clock()
            while i < len(due) and due[i] <= now:
                uid = self.submit(self.reqs[i], due=due[i])
                if t_open <= due[i] < t_end:
                    in_window.append(uid)
                i += 1
            if now >= t_end:
                self._stop_profile()
                waiting = [u for u in in_window
                           if eng.metrics[u]["first_token"] is None
                           and not eng.metrics[u]["status"].terminal]
                if not waiting or now > t_end + TAIL_WAIT_S:
                    break
            self._tick(now)
            if eng.idle:
                nxt = due[i] if i < len(due) else now + 0.01
                time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            for uid in self.step():
                self.info[uid]["done"] = self.clock()
            if i >= len(due) and now < t_end:
                raise RuntimeError("the mix's request pool ran dry inside "
                                   "the window; raise its 'requests'")
        return {"t_start": t_open, "t_end": t_end, "in_window": in_window}

    # -- results ----------------------------------------------------------
    def failed(self):
        from repro.serve import RequestStatus
        return sum(1 for u in self.info
                   if self.eng.metrics[u]["status"].terminal
                   and self.eng.metrics[u]["status"]
                   != RequestStatus.FINISHED)

    def served_greedy(self):
        """(uid, prompt, served tokens) of every greedy request that served
        a token: whole where it finished, and as far as it got where it was
        still running when the window closed.  Such a request is cancelled
        here, after the window and its counts, so that its tokens can be
        read; in a decode-heavy mix no request may finish inside one
        window."""
        from repro.serve import RequestStatus
        eng, out = self.eng, []
        for uid, inf in self.info.items():
            m = eng.metrics[uid]
            if inf["req"].temperature > 0.0 or not m["token_times"]:
                continue
            if not m["status"].terminal:
                eng.cancel(uid)
            elif m["status"] != RequestStatus.FINISHED:
                continue
            full = eng.collect(uid)
            plen = len(inf["req"].prompt)
            out.append((uid, full[:plen], full[plen:]))
        return out


def sample(served, seed: int):
    """The greedy request that served the most tokens, then others drawn
    from the seed, until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX``
    requests."""
    if not served:
        return []
    order = sorted(served, key=lambda f: (-len(f[2]), f[0]))
    picked, rest = [order[0]], order[1:]
    rng = np.random.default_rng(int(seed) + 1)
    for j in rng.permutation(len(rest)):
        if (len(picked) >= SAMPLE_MAX
                or sum(len(p[2]) for p in picked) >= SAMPLE_TOKENS):
            break
        picked.append(rest[j])
    return picked


def judge(model: dict, seed: int, picked, length: int, control=False):
    """Widest reference-logit gap of the served tokens; with ``control``,
    also that of the tokens a float8-weight reference puts first at the
    same positions.  → dict of gaps and the tokens compared."""
    seqs = [list(p) + list(s[:-1]) for _, p, s in picked]
    where = [np.arange(len(p) - 1, len(p) - 1 + len(s)) for _, p, s in picked]
    served = [np.asarray(s, np.int32) for _, _, s in picked]
    out = {"tokens": int(sum(len(s) for s in served))}
    firsts = None
    if control:
        hid = reference.final_hidden(model, seed, seqs, length, "fp8")
        w = reference.head(model, seed, "fp8")
        firsts = [reference.gaps(h, w, pos, t)[1]
                  for h, pos, t in zip(hid, where, served)]
        del hid, w
        gc.collect()
    hid = reference.final_hidden(model, seed, seqs, length)
    w = reference.head(model, seed)
    gap = [reference.gaps(h, w, pos, t)[0]
           for h, pos, t in zip(hid, where, served)]
    out["gap"] = float(max(g.max() for g in gap))
    if control:
        cgap = [reference.gaps(h, w, pos, t)[0]
                for h, pos, t in zip(hid, where, firsts)]
        out["control_gap"] = float(max(g.max() for g in cgap))
    del hid, w
    gc.collect()
    return out


def free(*trees):
    import jax
    for leaf in jax.tree.leaves(trees):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def e2e_values(drv: Driver, win: dict, loop: str) -> dict:
    eng = drv.eng
    if loop == "closed":
        return {"serve_tokens_per_s":
                win["tokens"] / (win["t_end"] - win["t_start"])}
    ttft, tpot = [], []
    for uid in win["in_window"]:
        m, due = eng.metrics[uid], drv.info[uid]["due"]
        ttft.append(math.inf if m["first_token"] is None
                    else m["first_token"] - due)
    for uid, inf in drv.info.items():
        m = eng.metrics[uid]
        done = m["finished"]
        n = len(m["token_times"])
        if (inf.get("done") is not None and done is not None
                and m["status"].value == "finished"
                and win["t_start"] <= done <= win["t_end"]
                and m["first_token"] is not None and n > 1):
            tpot.append((done - m["first_token"]) / (n - 1))
    return {"ttft_p90_ms": 1e3 * bench.quantile(ttft, 0.9),
            "tpot_p90_ms": 1e3 * bench.quantile(tpot, 0.9),
            "requests_in_window": len(ttft),
            "finished_in_window": len(tpot)}


def readings(drv: Driver, win: dict, model: dict, ecfg, summary, peak,
             tracer) -> dict:
    """What the per-layer readers read: the numbers below by name, and
    whole, ``trace`` (the ``trace.Summary`` of the traced part, or None),
    ``steps`` (a ``StepRecord`` per engine step) and ``requests`` (uid →
    the request, its due and submit times)."""
    r = {"num_slots": ecfg.num_slots, "trace": summary, "steps": drv.steps,
         "requests": drv.info,
         "peak.bf16_flops_per_s": peak["bf16_flops_per_s"],
         "peak.hbm_bytes_per_s": peak["hbm_bytes_per_s"]}
    in_win = [s for s in drv.steps
              if s.start >= win["t_start"] and s.end <= win["t_end"]]
    r["window.decode_iterations"] = sum(s.iterations for s in in_win)
    r["window.decode_tokens"] = sum(s.decode_tokens for s in in_win)
    third = len(in_win) // 3
    if third:
        r["window.waiting_first_third"] = float(np.mean(
            [s.waiting for s in in_win[:third]]))
        r["window.waiting_last_third"] = float(np.mean(
            [s.waiting for s in in_win[-third:]]))
    traced = [s for s in drv.steps if s.traced]
    r["traced.decode_iterations"] = sum(s.iterations for s in traced)
    ctx = [c for s in traced for c in s.contexts]
    r["traced.decode_flops"] = counts.decode_flops(model, ctx)
    r["traced.decode_bytes"] = counts.decode_bytes(
        model, r["traced.decode_iterations"], ctx)
    r["traced.prefill_flops"] = sum(counts.prefill_flops(model, p)
                                    for s in traced for p in s.prompts)
    if summary is not None:
        r["device.busy_s"] = summary.busy_s
        r["device.window_s"] = summary.window_s
        for k, v in summary.programs.items():
            r[f"device.program.{k}"] = v
    if "in_window" in win:
        # on the host clock: only requests due clear of the profiler's calls
        until = (drv.profile.host_until() if drv.profile is not None
                 else win["t_end"])
        first_prefill = {}
        for sp in tracer.spans():
            if sp.name == "engine.prefill":
                uid = sp.args.get("uid")
                first_prefill.setdefault(uid, sp.start)
        host = [u for u in win["in_window"] if drv.info[u]["due"] < until]
        r["host.requests"] = len(host)
        r["host.gen_lag_s"] = [drv.info[u]["submit"] - drv.info[u]["due"]
                               for u in host]
        # a request never prefilled, or never answered, waits for ever
        r["host.queue_wait_s"] = [
            first_prefill.get(u, math.inf) - drv.info[u]["due"]
            for u in host]
        first = {u: drv.eng.metrics[u]["first_token"] for u in host}
        r["host.ttft_s"] = [
            (math.inf if first[u] is None else first[u]) - drv.info[u]["due"]
            for u in host]
    return r


def report(drv: Driver, win: dict, r: dict) -> None:
    """Lines on standard error that say what the window held."""
    steps = [s for s in drv.steps
             if s.start >= win["t_start"] and s.end <= win["t_end"]]
    if steps:
        dt = [s.end - s.start for s in steps]
        longest = sorted(steps, key=lambda s: s.start - s.end)[:3]
        bench.say(f"{len(steps)} engine steps in the window: median "
                  f"{1e3 * float(np.median(dt)):.1f} ms; "
                  f"{sum(len(s.prompts) for s in steps)} prefills; longest "
                  "(s into the window, ms, prompts prefilled, iterations): "
                  + str([(round(s.start - win["t_start"], 2),
                          round(1e3 * (s.end - s.start), 1), s.prompts,
                          s.iterations) for s in longest]))
        slow = [round(1e3 * d, 1) for d in dt if d > 2 * np.median(dt)]
        bench.say(f"steps over twice the median: {len(slow)} {slow}")
    if "in_window" in win:
        first = [drv.eng.metrics[u]["first_token"] for u in win["in_window"]]
        ttft = [1e3 * ((math.inf if f is None else f) - drv.info[u]["due"])
                for u, f in zip(win["in_window"], first)]
        bench.say("ttft ms over the window's requests, quantiles .5 .75 .9 "
                  ".95 1: " + str([round(bench.quantile(ttft, q), 1)
                                   for q in (0.5, 0.75, 0.9, 0.95, 1.0)]))
    if drv.profile is not None:
        bench.say(f"the profiler held the host (s): {drv.profile.held_s}")
    if "host.requests" in r:
        p90 = {k: round(1e3 * bench.quantile(r[f"host.{k}_s"], 0.9), 1)
               for k in ("gen_lag", "queue_wait", "ttft")}
        bench.say(f"host clock, the {r['host.requests']} requests due clear "
                  f"of the profiler, p90 ms: {p90}")


@dataclasses.dataclass
class Served:
    """What one window left: the metric values, the per-layer readings,
    the device summary and the sample the comparison will judge."""
    values: dict
    readings: dict
    summary: object
    picked: list
    attempted: int
    failed: int
    peak_mem: object
    compiles_in_window: int


def serve(cell, args, devs, clock, t_proc0) -> Served:
    """Set-up, the window and its readings; the engine's state and the
    weights are freed before this returns."""
    from repro.obs.trace import Tracer
    from repro.serve import Engine, EngineConfig

    spec, mix, shape = cell.config, cell.mix, cell.config["engine"]
    cfg = model_config(spec)
    reqs = traffic.generate(mix, args.seed, spec["vocab_size"])
    params = weights.make_params(cfg, args.seed)
    ecfg = EngineConfig(num_slots=shape["num_slots"],
                        page_size=shape["page_size"],
                        max_seq=shape["max_seq"],
                        min_bucket=shape["min_bucket"],
                        seed=args.seed % 2 ** 32)
    if mix.get("start") == "mid-request":
        reqs = traffic.mid_request(reqs, ecfg.num_slots, args.seed,
                                   spec["vocab_size"])
    tracer = Tracer()
    eng = Engine(cfg, params, ecfg, tracer=tracer)
    drv = Driver(eng, reqs, mix)
    buckets = drv.warm_up(ecfg)
    if mix["loop"] == "closed":
        for _ in range(int(mix["clients_per_slot"]) * ecfg.num_slots):
            drv.submit_next()
        drv.step()
    gc_clock = bench.GcClock()
    gc_clock.settle()
    compiles0 = clock.compiles
    setup_s = time.perf_counter() - t_proc0
    bench.say(f"set-up {setup_s:.1f}s: buckets {buckets}, {clock.compiles} "
              f"compiles ({clock.seconds:.1f}s, {clock.hits} cache hits)")

    root = cell.root if args.trace else None
    if mix["loop"] == "closed":
        win = drv.closed(args.seconds, root)
    else:
        win = drv.poisson(args.seconds, root)
    in_window = clock.compiles - compiles0
    values = e2e_values(drv, win, mix["loop"])
    values["setup_s"] = setup_s
    peak_mem = bench.peak_bytes(devs)
    failed, attempted = drv.failed(), len(drv.info)
    picked = sample(drv.served_greedy(), args.seed)
    bench.say(f"window {win['t_end'] - win['t_start']:.2f}s: {values}; "
              f"{in_window} compiles inside; peak {peak_mem} bytes; "
              f"{attempted} requests, {failed} failed")
    summary = drv.profile.summary() if drv.profile is not None else None
    r = readings(drv, win, spec, ecfg, summary,
                 counts.peaks(devs[0].device_kind), tracer)
    report(drv, win, r)
    bench.say(gc_clock.close())
    free(eng.caches, eng._state, params)
    return Served(values, r, summary, picked, attempted, failed, peak_mem,
                  in_window)


def checks(spec: dict, gap, tokens: int, failed: int) -> dict:
    """The numbers ``correct`` compares, each beside the configuration's
    limit: the widest reference-logit gap, the served tokens compared, the
    requests that failed."""
    lim = spec["correct"]
    return {"logit_gap_max": bench.check(gap, lim["logit_gap_max"]),
            "tokens_compared": bench.check(tokens, lim["min_tokens"],
                                           at_least=True),
            "failed_requests": bench.check(failed, 0)}


def limit_readings(cell, args, devs, clock, control: bool) -> dict:
    """A run of the cell's own traffic, judged, without the result line:
    the program's checks, and with ``control`` the float8 control's, its
    gap put in the program's place, each under the cell's own limits."""
    s = serve(cell, args, devs, clock, time.perf_counter())
    spec = cell.config
    j = judge(spec, args.seed, s.picked, spec["engine"]["max_seq"],
              control=control)
    out = {"program": checks(spec, j["gap"], j["tokens"], s.failed),
           "values": s.values}
    if control:
        out["control"] = checks(spec, j["control_gap"], j["tokens"],
                                s.failed)
    return out


def run(cell, args, devs, clock, t_proc0):
    """One run of a serving cell → (result dict, checks dict)."""
    s = serve(cell, args, devs, clock, t_proc0)
    spec = cell.config
    judged = judge(spec, args.seed, s.picked, spec["engine"]["max_seq"]) \
        if s.picked else {"gap": None, "tokens": 0}
    got = checks(spec, judged["gap"], judged["tokens"], s.failed)
    result = {"correct": bench.passes(got), "attempted": s.attempted,
              "failed": s.failed}
    dev = bench.device_record(devs, s.peak_mem)
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = bench.load_reader(cell.root, m["name"])(s.readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = s.summary.busy_s, s.summary.window_s
        result["breakdown"] = {"device_ops": s.summary.device_ops,
                               "idle_gaps": s.summary.idle_gaps}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": s.values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=dev,
                  compiles_in_window=s.compiles_in_window)
    return result, got
