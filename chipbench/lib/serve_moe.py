"""Serving cells of an MLA + MoE model (DeepSeek-V2): the engine under the
mix's closed loop as ``serve.py`` drives a dense model, with what is its
own here:

* the configuration is checked against DeepSeek-V2's published keys, and
  the weights come from ``moe_weights`` (MLA's norm gains, each held
  expert's fan-in);
* the mix is ``serve.py``'s, with the lengths in one order for every
  seed (``pool``, ``mid_request``): each seed then does the same work;
* the readings add the engine's expert counters (``serve.moe_*``) and
  latent pages per step, the device time of the ``mla`` and ``moe`` named
  scopes inside the decode segment (read from the traced window's
  ``.xplane.pb`` with ``scopes.py`` before it is removed), and put the MLA
  + MoE counts of ``mla_counts`` under ``traced.decode_bytes`` /
  ``traced.decode_flops``;
* ``correct`` is judged with ``mla_reference``: the mean gap, the tokens
  compared and the failed requests (``checks``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from chipbench.lib import (bench, counts, mla_counts, mla_reference,
                           moe_weights, reference, serve, traffic)
from chipbench.lib import scopes as scopes_lib
from chipbench.lib import trace as trace_lib

SCOPES = ("mla", "moe", "moe.route", "ops.grouped_matmul", "sample",
          "lm_head")


def model_config(spec: dict):
    """The repository's configuration with the file's overrides, checked
    against the published keys the file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(spec["repo_config"]),
                              **spec.get("repo_overrides", {}))
    y = cfg.rope_yarn
    pairs = {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.head_dim, "v_head_dim": cfg.head_dim,
        "qk_rope_head_dim": cfg.rope_head_dim,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts": cfg.held_experts,
        "n_routed_experts_published": cfg.num_experts,
        "first_expert_held": cfg.first_expert,
        "n_shared_experts": cfg.num_shared_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "first_k_dense_replace": cfg.first_k_dense,
        "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None if y is None else {
            "type": "yarn", "factor": y.factor,
            "original_max_position_embeddings": y.original_max_pos,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim}}
    bad = {k: (spec.get(k), v) for k, v in pairs.items() if spec.get(k) != v}
    if bad:
        raise ValueError(f"{spec['name']}: file and program disagree "
                         f"(file, program): {bad}")
    return cfg


def golden_ranks(n: int) -> np.ndarray:
    """Request i's rank in the quantile grid: the rank of frac((i + 1)·φ⁻¹)
    among the n such numbers, so that any run of consecutive requests
    spreads over the whole distribution (a golden-ratio sequence)."""
    u = np.mod((np.arange(n) + 1) * ((math.sqrt(5.0) - 1.0) / 2.0), 1.0)
    return np.argsort(np.argsort(u))


def pool(mix: dict, seed: int, vocab: int) -> list:
    """``traffic.generate``'s requests for a closed loop: the same prompt
    and output lengths (the mix's quantile grid) and the same share of
    sampled requests, but the lengths in the one order ``golden_ranks``
    gives, whatever the seed; the seed draws the tokens and which requests
    sample.  A window admits about a dozen requests, and a prompt past
    4,096 tokens (an 8,192-token prefill, 1.86 s on a v5e) takes 3.7% of
    a 50-s window: drawn in the seed's order, the window's prefills made
    ``serve_tokens_per_s`` spread by 5–8% between seeds."""
    n = int(mix["requests"])
    rank = golden_ranks(n)
    plen = traffic.sizes(mix["prompt"], n)[rank]
    olen = traffic.sizes(mix["output"], n)[rank]
    s = mix["sampled"]
    rng = np.random.default_rng(int(seed))
    sampled = rng.permutation(np.arange(n) % int(s["every"]) == 0)
    return [traffic.Request(
        prompt=tuple(int(t) for t in rng.integers(0, vocab, plen[i])),
        max_new=int(olen[i]),
        temperature=float(s["temperature"]) if sampled[i] else 0.0,
        top_p=float(s["top_p"]) if sampled[i] else 1.0, due=0.0)
        for i in range(n)]


def mid_request(reqs: list, n: int, seed: int, vocab: int) -> list:
    """``traffic.mid_request`` with request k's served fraction (k + ½)/n
    in slot order: beside ``pool``'s output lengths, the first n requests'
    (length, fraction) pairs form a golden-ratio lattice, and the slots
    come free at the same steps for every seed."""
    rng = np.random.default_rng([int(seed), 1])
    out = list(reqs)
    for k, r in enumerate(reqs[:n]):
        done = int((k + 0.5) / n * r.max_new)
        served = tuple(int(t) for t in rng.integers(0, vocab, done))
        out[k] = dataclasses.replace(r, prompt=r.prompt + served,
                                     max_new=r.max_new - done)
    return out


# XLA lowers ``lax.ragged_dot`` on a TPU to kernels whose operations carry
# their own names ("ragged-dot-…") in place of the program's and the
# scopes' path
KERNEL_OPS = "ragged-dot"


def decode_scopes(planes: dict, w0: float, w1: float) -> dict:
    """``scopes.scopes``, with the ragged-dot kernel operations that the
    decode segment runs counted in its ``moe`` scope: a device runs one
    program at a time, so such an operation belongs to the program of the
    last operation before it that names one.  The union of the scope's own
    operations and those kernels, the mean over the planes."""
    out = scopes_lib.scopes(planes, w0, w1)
    total = 0.0
    for plane in planes.values():
        moe, program = [], None
        for op in sorted(plane["ops"], key=lambda o: o.start):
            if not op.path or op.end <= w0 or op.start >= w1:
                continue
            if op.path.startswith(KERNEL_OPS):
                if program == "_decode_segment":
                    moe.append((op.start, op.end))
                continue
            program, names = scopes_lib.components(op.path)
            if program == "_decode_segment" and "moe" in names:
                moe.append((op.start, op.end))
        total += scopes_lib._seconds(moe, w0, w1)
    if total:
        out.setdefault("_decode_segment", {})["moe"] = total / max(
            len(planes), 1)
    return out


class ScopedProfile(trace_lib.Profile):
    """``trace.Profile`` over the window's last ``TRACE_SECONDS`` (not 4 s:
    an engine step that admits an 8k-token prompt lasts seconds, and the
    profiler starts only between steps), that also reads, before the trace
    is removed, the device time of each named scope of each program."""
    TRACE_SECONDS = 8.0
    scopes: dict = {}

    def __init__(self, root, t_start: float, seconds: float):
        super().__init__(root, t_start, seconds)
        self.t_on = t_start + seconds - min(self.TRACE_SECONDS, seconds / 2)

    def summary(self):
        self.stop()
        try:
            found = sorted(self.dir.rglob("*.xplane.pb"))
            if not found:
                raise RuntimeError("no profile: no engine step began inside "
                                   "the traced part of the window")
            path = found[-1]
            devices, host = trace_lib.load(path)
            w0, w1 = scopes_lib.window(host)
            self.scopes = decode_scopes(scopes_lib.read_planes(path), w0, w1)
            return trace_lib.summarize(devices, host)
        finally:
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)


class Driver(serve.Driver):
    """``serve.Driver`` that also keeps, per engine step, what the
    engine's decode counters added."""
    COUNTERS = ("moe_assignments", "moe_experts_touched", "decode_kv_pages")

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.added = []

    def step(self):
        before = [getattr(self.eng, c) for c in self.COUNTERS]
        newly = super().step()
        self.added.append([getattr(self.eng, c) - b
                           for c, b in zip(self.COUNTERS, before)])
        return newly


def readings(drv: Driver, win: dict, spec: dict, ecfg, summary, peak,
             tracer, scopes: dict) -> dict:
    """``serve.readings``, with the MLA + MoE counts in place of the dense
    ones and, by name: ``traced.moe_assignments``,
    ``traced.moe_experts_touched``, ``traced.kv_pages`` (the engine's
    counters over the traced steps), ``traced.mla_bytes`` / ``_flops``,
    ``traced.moe_bytes`` / ``_flops`` and
    ``device.scope._decode_segment.<scope>`` (seconds)."""
    # serve.readings also counts the dense way, from a head width; those
    # counts are replaced below
    dense = dict(spec, head_dim=spec["v_head_dim"])
    r = serve.readings(drv, win, dense, ecfg, summary, peak, tracer)
    traced = [i for i, s in enumerate(drv.steps) if s.traced]
    added = np.sum([drv.added[i] for i in traced], axis=0) if traced \
        else np.zeros(3, int)
    assign, touched, pages = (int(v) for v in added)
    it = r["traced.decode_iterations"]
    ctx = [c for i in traced for c in drv.steps[i].contexts]
    r["traced.moe_assignments"] = assign
    r["traced.moe_experts_touched"] = touched
    r["traced.kv_pages"] = pages
    r["traced.mla_bytes"], r["traced.mla_flops"] = mla_counts.mla_decode(
        spec, it, ctx)
    r["traced.moe_bytes"], r["traced.moe_flops"] = mla_counts.moe_decode(
        spec, it, len(ctx), assign, touched)
    r["traced.decode_bytes"], r["traced.decode_flops"] = \
        mla_counts.decode_step(spec, it, ctx, assign, touched)
    r["traced.prefill_flops"] = None     # dense counts; none read here
    for name, s in scopes.get("_decode_segment", {}).items():
        if name in SCOPES:
            r[f"device.scope._decode_segment.{name}"] = s
    return r


def serve_window(cell, args, devs, clock, t_proc0) -> serve.Served:
    """Set-up, the window and its readings (``serve.serve`` for this
    model); the engine's state and the weights are freed before this
    returns."""
    from repro.obs.trace import Tracer
    from repro.serve import Engine, EngineConfig

    spec, shape = cell.config, cell.config["engine"]
    cfg = model_config(spec)
    mix = dict(cell.mix, kind="serve")
    if mix["loop"] != "closed":
        raise ValueError(f"{cell.name}: this driver runs closed loops")
    reqs = pool(mix, args.seed, spec["vocab_size"])
    params = moe_weights.make_params(cfg, args.seed)
    ecfg = EngineConfig(num_slots=shape["num_slots"],
                        page_size=shape["page_size"],
                        max_seq=shape["max_seq"],
                        min_bucket=shape["min_bucket"],
                        seed=args.seed % 2 ** 32)
    if mix.get("start") == "mid-request":
        reqs = mid_request(reqs, ecfg.num_slots, args.seed,
                           spec["vocab_size"])
    tracer = Tracer()
    eng = Engine(cfg, params, ecfg, tracer=tracer)
    drv = Driver(eng, reqs, mix)
    buckets = drv.warm_up(ecfg)
    for _ in range(int(mix["clients_per_slot"]) * ecfg.num_slots):
        drv.submit_next()
    drv.step()
    gc_clock = bench.GcClock()
    gc_clock.settle()
    compiles0 = clock.compiles
    setup_s = time.perf_counter() - t_proc0
    bench.say(f"set-up {setup_s:.1f}s: buckets {buckets}, {clock.compiles} "
              f"compiles ({clock.seconds:.1f}s, {clock.hits} cache hits)")

    profile = None
    if args.trace:
        profile = ScopedProfile(cell.root, drv.clock(), args.seconds)
        drv.profile = profile
    win = drv.closed(args.seconds)
    in_window = clock.compiles - compiles0
    values = serve.e2e_values(drv, win, "closed")
    values["setup_s"] = setup_s
    peak_mem = bench.peak_bytes(devs)
    failed, attempted = drv.failed(), len(drv.info)
    picked = serve.sample(drv.served_greedy(), args.seed)
    bench.say(f"window {win['t_end'] - win['t_start']:.2f}s: {values}; "
              f"{in_window} compiles inside; peak {peak_mem} bytes; "
              f"{attempted} requests, {failed} failed")
    summary = profile.summary() if profile is not None else None
    r = readings(drv, win, spec, ecfg, summary,
                 counts.peaks(devs[0].device_kind), tracer,
                 profile.scopes if profile is not None else {})
    serve.report(drv, win, r)
    if profile is not None:
        bench.say("decode segment by scope (s): " + str(
            {k: round(v, 4) for k, v in r.items()
             if k.startswith("device.scope.")}))
    bench.say(f"engine counters over the run: moe_assignments "
              f"{eng.moe_assignments}, moe_experts_touched "
              f"{eng.moe_experts_touched}, decode_kv_pages "
              f"{eng.decode_kv_pages}, decode_iterations "
              f"{eng.decode_iterations}")
    bench.say(gc_clock.close())
    serve.free(eng.caches, eng._state, params)
    return serve.Served(values, r, summary, picked, attempted, failed,
                        peak_mem, in_window)


# positions from here on lie past YaRN's original context and the prefill's
# first block of keys (``attention_xla_chunked``): the judge reads them apart
FAR = 4096


def _gap_stats(g: np.ndarray, pos: np.ndarray) -> dict:
    """One side's gaps: the widest, the mean, and the share of tokens that
    are not the reference's best, over all positions and past ``FAR``."""
    far = pos >= FAR
    return {"gap": float(g.max()), "gap_mean": float(g.mean()),
            "off_best": float(np.mean(g > 0)),
            "off_best_far": float(np.mean(g[far] > 0)) if far.any() else None,
            "far_tokens": int(far.sum())}


def judge(model: dict, seed: int, picked, control=False, witness=False):
    """``serve.judge`` with the MLA + MoE reference, over the sampled
    sequences padded to the engine's ``max_seq`` rounded up to
    ``mla_reference.BLOCK_Q`` (one length, so one compile of the reference
    serves every run).  → {"tokens", "program": stats, and with ``control``
    "control", with ``witness`` "bf16": the same stats of the tokens the
    float8 control, or the reference itself in bfloat16, would serve}, each
    stats ``_gap_stats`` against the float32 reference; also the share of
    compared tokens whose held-expert picks in some layer turn on a near-tie
    of the router input's rounding (``mla_reference``)."""
    seqs = [list(p) + list(s[:-1]) for _, p, s in picked]
    where = [np.arange(len(p) - 1, len(p) - 1 + len(s)) for _, p, s in picked]
    served = [np.asarray(s, np.int32) for _, _, s in picked]
    blk = mla_reference.BLOCK_Q
    length = -(-model["engine"]["max_seq"] // blk) * blk
    sides = {"program": served}
    for side, rounding, on in (("control", "fp8", control),
                               ("bf16", "bf16", witness)):
        if not on:
            continue
        hid, _ = mla_reference.final_hidden(model, seed, seqs, length,
                                            rounding)
        w = reference.head(model, seed, rounding if rounding == "fp8"
                           else None)
        sides[side] = [reference.gaps(h, w, pos, t)[1]
                       for h, pos, t in zip(hid, where, served)]
        del hid, w
        gc.collect()
    hid, flips = mla_reference.final_hidden(model, seed, seqs, length)
    w = reference.head(model, seed)
    pos = np.concatenate(where)
    out = {"tokens": int(sum(len(s) for s in served))}
    for side, toks in sides.items():
        g = np.concatenate([reference.gaps(h, w, p, t)[0]
                            for h, p, t in zip(hid, where, toks)])
        out[side] = _gap_stats(g, pos)
        bench.say(f"{side}: {out[side]}; gap quantiles .5 .9 .99 "
                  f"{np.quantile(g, [0.5, 0.9, 0.99]).round(4).tolist()}")
    near = np.concatenate([f[:, p].any(0) for f, p in zip(flips, where)])
    out["near_tie_share"] = float(np.mean(near))
    del hid, w
    gc.collect()
    bench.say(f"judged {out['tokens']} tokens of {len(picked)} requests at "
              f"length {length}; tokens whose held-expert picks turn on a "
              f"near-tie in some layer {100 * out['near_tie_share']:.2f}%")
    return out


def checks(spec: dict, stats, tokens: int, failed: int) -> dict:
    """The numbers ``correct`` compares, each beside the configuration's
    limit: the mean reference-logit gap of the served tokens, the tokens
    compared, the requests that failed.  The widest gap is not among them:
    with random weights the router's top-6 of 160 lies close to ties, a
    token whose route bfloat16 rounding swaps moves by as much as float8
    weights move one, and the widest gap of the program and of the float8
    control overlap (PERF.md §2)."""
    lim = spec["correct"]
    return {"logit_gap_mean": bench.check(
                None if stats is None else stats["gap_mean"],
                lim["logit_gap_mean"]),
            "tokens_compared": bench.check(tokens, lim["min_tokens"],
                                           at_least=True),
            "failed_requests": bench.check(failed, 0)}


def limit_readings(cell, args, devs, clock, control: bool) -> dict:
    """``serve.limit_readings`` for this model, with the bfloat16
    reference's readings beside the program's under ``values``."""
    s = serve_window(cell, args, devs, clock, time.perf_counter())
    spec = cell.config
    j = judge(spec, args.seed, s.picked, control=control, witness=True)
    values = dict(s.values, near_tie_share=j["near_tie_share"])
    for side in ("program", "bf16"):
        values.update({f"{side}.{k}": v for k, v in j[side].items()})
    out = {"program": checks(spec, j["program"], j["tokens"], s.failed),
           "values": values}
    if control:
        out["control"] = checks(spec, j["control"], j["tokens"], s.failed)
        values.update({f"control.{k}": v for k, v in j["control"].items()})
    return out


def run(cell, args, devs, clock, t_proc0):
    """One run of the cell → (result dict, checks dict)."""
    s = serve_window(cell, args, devs, clock, t_proc0)
    spec = cell.config
    judged = judge(spec, args.seed, s.picked) if s.picked \
        else {"program": None, "tokens": 0}
    got = checks(spec, judged["program"], judged["tokens"], s.failed)
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
