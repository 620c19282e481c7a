"""Training cells: the program's own train step under load, its end-to-end
metric, the readings its per-layer metrics take, and the comparison with
the reference that decides ``correct``.

The timed path is what ``repro.train.train`` builds: ``make_train_step``
under ``jax.jit`` with the parameters and optimizer state donated, fed by
``SyntheticCorpus.prefetching`` through ``make_global_batch``.  Set-up
draws the float32 master weights on the device from the seed, builds that
one step and its AdamW state, and drives it through its first
``checked_steps`` steps with the window's own call and feed.  That compiles
the step (or loads it from the persistent cache) and gives the program's
readings: each step's loss, the first gradient as the optimizer took it
(its first moment after one step ÷ (1 − β1)), and each leaf's change after
the checked steps.  The window then goes on with the same object, at most
two steps in flight, and ends on ``block_until_ready``.

After the window the peak of device memory is read, the program's state
is freed, and the float32 reference (``train_reference.py``) follows the
checked steps on the same batches.  Compared, by the worst leaf where the
number is a norm (the gap between the two norms over the larger of the
reference's norm of that leaf and of the median leaf): the loss of each
checked step, the first gradient, and the change.  A leaf whose reference
gradient is under ``NOUGHT`` of the median leaf's moves by round-off alone
under Adam and is left out of the change.
"""
from __future__ import annotations

import collections
import dataclasses
import statistics
import time

import numpy as np

from chipbench.lib import bench, counts, serve, train_reference, weights
from chipbench.lib import trace as trace_lib

IN_FLIGHT = 2
NOUGHT = 1e-3


def configs(spec: dict, job: dict):
    """The program's model and train configurations for a cell: the
    optimizer's settings come from the job file, as the reference's do."""
    from repro.optim import AdamWConfig
    from repro.train import TrainConfig
    cfg = serve.model_config(spec)
    o, s = job["adamw"], job["schedule"]
    tcfg = TrainConfig(
        peak_lr=job["peak_lr"], warmup_steps=s["warmup_steps"],
        total_steps=s["total_steps"], schedule=s["kind"],
        adamw=AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          clip_norm=o["clip_norm"]))
    return cfg, tcfg


def leaf_norms(tree) -> dict:
    """Per leaf path of a program tree, the leaf's norm."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {weights._path_str(p): float(n) for (p, _), n in zip(flat, norms)}


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |prog − ref| / max(ref, the median leaf's ref)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers ``correct`` compares, and the leaves counted."""
    for k in ("grad", "change"):
        if set(prog[k]) != set(ref[k]):
            raise ValueError(f"{k}: the program's leaves "
                             f"{sorted(set(prog[k]) ^ set(ref[k]))} and the "
                             "reference's differ")
    med = statistics.median(ref["grad_raw"].values())
    moved = {k for k, v in ref["grad_raw"].items() if v >= NOUGHT * med}
    return {"loss_gap": max(abs(a - b) for a, b in zip(prog["loss"],
                                                       ref["loss"])),
            "grad_norm_gap": worst_leaf(prog["grad"], ref["grad"]),
            "update_norm_gap": worst_leaf(prog["change"], ref["change"],
                                          moved),
            "leaves_left_out": sorted(set(ref["grad_raw"]) - moved)}


@dataclasses.dataclass
class Trained:
    """What one run left: metric values, readings, the device summary, the
    program's checked readings and the batches they were taken on."""
    values: dict
    readings: dict
    summary: object
    checked: dict
    batches: list
    steps: int
    peak_mem: object
    compiles_in_window: int


class Loop:
    """One jitted step, its state and its feed, driven one call at a time."""

    def __init__(self, step_fn, params, opt, feed, clock=time.perf_counter):
        self.step_fn, self.params, self.opt, self.feed = (step_fn, params,
                                                          opt, feed)
        self.clock, self.step, self.pending = clock, 0, collections.deque()
        self.done_at = []       # host time at which each waited step ended

    def call(self, keep_batch=False):
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation
        from repro.data.pipeline import make_global_batch
        with TraceAnnotation("bench.train_step"):
            host = next(self.feed)
            self.params, self.opt, m = self.step_fn(
                self.params, self.opt, make_global_batch(host),
                jnp.int32(self.step))
        self.step += 1
        self.pending.append(m["loss"])
        if len(self.pending) > IN_FLIGHT:
            self.pending.popleft().block_until_ready()
            self.done_at.append(self.clock())
        return (m, host) if keep_batch else m

    def drain(self):
        import jax
        jax.block_until_ready((self.params, self.opt))
        self.pending.clear()


def train(cell, args, devs, clock, t_proc0, step_fn=None) -> Trained:
    """Set-up, the window and its readings; the program's state is freed
    before this returns.  ``step_fn`` replaces the program's step (tests
    plant faults with it)."""
    import jax
    from repro.data import DataConfig, SyntheticCorpus
    from repro.optim import adamw
    from repro.train import make_train_step

    spec, job = cell.config, cell.mix
    cfg, tcfg = configs(spec, job)
    b, s = int(job["batch"]), int(job["seq"])
    corpus = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=s, global_batch=b,
                                        seed=int(args.seed)))
    feed = corpus.prefetching(depth=IN_FLIGHT)
    params = weights.make_params(cfg, args.seed, dtype=np.float32)
    opt = adamw.init_state(params, tcfg.adamw)
    if step_fn is None:
        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    loop = Loop(step_fn, params, opt, feed)
    del params, opt

    # the checked steps, through the window's own call and feed
    losses, batches, grad = [], [], None
    for i in range(int(job["checked_steps"])):
        m, host = loop.call(keep_batch=True)
        losses.append(float(m["loss"]))
        batches.append(host)
        if i == 0:
            b1 = tcfg.adamw.b1
            grad = {k: v / (1 - b1) for k, v in
                    leaf_norms(loop.opt["mu"]).items()}
    loop.drain()
    change = weights.distances(loop.params, args.seed, np.float32)
    checked = {"loss": losses, "grad": grad, "change": change}
    gc_clock = bench.GcClock()
    gc_clock.settle()
    compiles0 = clock.compiles
    setup_s = time.perf_counter() - t_proc0
    bench.say(f"set-up {setup_s:.1f}s: {clock.compiles} compiles "
              f"({clock.seconds:.1f}s, {clock.hits} cache hits); checked "
              f"losses {losses}")

    t0 = loop.clock()
    t_end = t0 + args.seconds
    prof = trace_lib.Profile(cell.root, t0, args.seconds) \
        if args.trace else None
    first = loop.step
    while True:
        now = loop.clock()
        if now >= t_end:
            break
        if prof is not None:
            prof.tick(now)
        loop.call()
    loop.drain()
    t1 = loop.clock()
    if prof is not None:
        prof.stop()
    steps = loop.step - first
    in_window = clock.compiles - compiles0
    values = {"train_tokens_per_s": steps * b * s / (t1 - t0),
              "setup_s": setup_s}
    peak_mem = bench.peak_bytes(devs)
    bench.say(f"window {t1 - t0:.2f}s: {steps} steps, {values}; "
              f"{in_window} compiles inside; peak {peak_mem} bytes")
    gaps = np.diff([t for t in loop.done_at if t >= t0])
    if len(gaps):
        med = float(np.median(gaps))
        bench.say(f"steps ended {1e3 * med:.2f} ms apart (median), "
                  f"{1e3 * float(gaps.min()):.2f} to "
                  f"{1e3 * float(gaps.max()):.2f} ms; "
                  f"{int((gaps > 1.05 * med).sum())} over 5% late")
    if prof is not None:
        bench.say(f"the profiler held the host (s): {prof.held_s}")
    bench.say(gc_clock.close())
    summary = prof.summary() if prof is not None else None
    peak = counts.peaks(devs[0].device_kind)
    r = {"peak.bf16_flops_per_s": peak["bf16_flops_per_s"],
         "train.step_flops": counts.train_flops(spec, b, s),
         "trace": summary, "step_ends": loop.done_at}
    if summary is not None:
        r["device.busy_s"] = summary.busy_s
        r["device.window_s"] = summary.window_s
        for k, (n, t) in summary.calls.items():
            r[f"device.calls.{k}"] = n
            r[f"device.calls_s.{k}"] = t
    serve.free(loop.params, loop.opt)
    feed.close()
    return Trained(values, r, summary, checked, batches, steps, peak_mem,
                   in_window)


def checks(spec: dict, got: dict) -> dict:
    """The numbers ``correct`` compares, each beside the configuration's
    limit."""
    return {k: bench.check(got[k], spec["correct"][k])
            for k in ("loss_gap", "grad_norm_gap", "update_norm_gap")}


def limit_readings(cell, args, devs, clock, control: bool) -> dict:
    """The checked steps of one seed, judged, without the result line: the
    program's checks, and with ``control`` those of the control (the
    reference keeping its weights and moments in bfloat16) and of the
    reference with half of each batch left out, each put in the program's
    place, under the cell's own limits."""
    t = train(cell, args, devs, clock, time.perf_counter())
    spec, job = cell.config, cell.mix
    ref = train_reference.run(spec, job, args.seed, t.batches)
    out = {"program": checks(spec, compare(t.checked, ref)),
           "values": t.values,
           "losses": {"program": t.checked["loss"], "reference": ref["loss"]}}
    if control:
        out["control"] = checks(spec, compare(train_reference.run(
            spec, job, args.seed, t.batches, state="bfloat16"), ref))
        out["half_batch"] = checks(spec, compare(train_reference.run(
            spec, job, args.seed, t.batches, rows=job["batch"] // 2), ref))
    return out


def run(cell, args, devs, clock, t_proc0):
    """One run of a training cell → (result dict, checks dict)."""
    t = train(cell, args, devs, clock, t_proc0)
    ref = train_reference.run(cell.config, cell.mix, args.seed, t.batches)
    got = checks(cell.config, compare(t.checked, ref))
    result = {"correct": bench.passes(got), "attempted": t.steps,
              "failed": 0}
    dev = bench.device_record(devs, t.peak_mem)
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = bench.load_reader(cell.root, m["name"])(t.readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = t.summary.busy_s, t.summary.window_s
        result["breakdown"] = {"device_ops": t.summary.device_ops,
                               "idle_gaps": t.summary.idle_gaps}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": t.values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=dev,
                  compiles_in_window=t.compiles_in_window)
    return result, got
