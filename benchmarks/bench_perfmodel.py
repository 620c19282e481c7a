"""Paper Fig. 6 — performance-model fidelity.

The paper's claim: the top-5 model-ranked loop_spec_strings always contain
the measured-best schedule.  Here the *measured* side is the PARLOOPER
executor JIT-compiled by XLA:CPU (schedule differences are real wall-clock
differences on this host), and the *model* side is the TPU-adapted schedule
simulator scoring the same spec strings.  Derived metric: Spearman rank
correlation + top-5 containment.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (LoopSpec, TensorMap, ThreadedLoop, autotune,
                        perf_model, tpp)
from repro.launch.compile_cache import enable_compile_cache


def _measure_spec(spec, loops, A, B, k_step, bm, bk, bn, nb, mb):
    tl = ThreadedLoop(loops, spec, reduction_letters=("a",))

    def body(ind, C):
        ik, im, inn = ind
        a = jax.lax.dynamic_slice(A, (im, ik, 0, 0), (1, k_step, bm, bk))[0]
        b = jax.lax.dynamic_slice(B, (inn, ik, 0, 0), (1, k_step, bk, bn))[0]
        acc = tpp.brgemm(a, b)
        prev = jax.lax.dynamic_slice(C, (inn, im, 0, 0), (1, 1, bm, bn))[0, 0]
        c2 = jnp.where(ik == 0, acc, prev + acc)
        return jax.lax.dynamic_update_slice(C, c2[None, None], (inn, im, 0, 0))

    # lax mode: the nest lowers to real fori_loops, so the schedule
    # survives XLA:CPU optimization into the executable (unrolled nests get
    # re-fused/reordered and all schedules measure identically)
    f = jax.jit(lambda: tl(body, carry=jnp.zeros((nb, mb, bm, bn),
                                                 jnp.float32), mode="lax"))
    f().block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        f().block_until_ready()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]  # median: robust to host noise


def run():
    rng = np.random.default_rng(0)
    bm, bk, bn = 64, 64, 64
    mb, kb, nb = 8, 8, 8
    k_step = 2
    A = jnp.asarray(rng.normal(size=(mb, kb, bm, bk)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(nb, kb, bk, bn)).astype(np.float32))
    loops = [LoopSpec(0, kb, k_step, block_steps=(4,), name="k"),
             LoopSpec(0, mb, 1, block_steps=(4,), name="m"),
             LoopSpec(0, nb, 1, block_steps=(4,), name="n")]
    in_maps = [TensorMap(("b", "a"), (bm, bk)), TensorMap(("c", "a"), (bk, bn))]
    out_map = TensorMap(("c", "b"), (bm, bn))

    # the measured side runs on THIS host, so the model is parameterized
    # as the paper does for CPUs (§II-E): scalar-ish peak, DRAM bandwidth,
    # an L2-sized LRU working set, trace mode
    cpu_target = perf_model.TpuTarget(
        name="host_cpu", peak_flops_bf16=5e10, peak_flops_fp32=5e10,
        hbm_bw=2e10, vmem_bytes=1 * 2 ** 20, ici_bw=1e9, dma_latency=2e-7)
    cands = autotune.generate_candidates(
        loops, max_blockings=[2, 2, 2], parallel_letters=(),
        max_candidates=24, seed=3)
    rows = []
    preds, meas = [], []
    for c in cands:
        tl = ThreadedLoop(c.loops, c.spec_string, reduction_letters=("a",))
        rep = perf_model.predict(
            tl.nest, in_maps, out_map, dtype=np.float32,
            flops_per_body=2 * bm * bk * bn * k_step,
            tile_mnk=(bm, bn, bk), reduction_letters=("a",),
            target=cpu_target, mode="trace")
        t = _measure_spec(c.spec_string, c.loops, A, B, k_step, bm, bk, bn,
                          nb, mb)
        preds.append(rep.total_time)
        meas.append(t)

    preds, meas = np.array(preds), np.array(meas)
    rp = np.argsort(np.argsort(preds))
    rm = np.argsort(np.argsort(meas))
    spearman = float(np.corrcoef(rp, rm)[0, 1])
    top5 = set(np.argsort(preds)[:5])
    best = int(np.argmin(meas))
    contained = best in top5
    rows.append(("perfmodel_fig6_spearman", float(np.mean(meas)) * 1e6,
                 f"spearman={spearman:.3f}"))
    rows.append(("perfmodel_fig6_top5_contains_best",
                 float(np.mean(meas)) * 1e6, f"contained={contained}"))

    # platform-neutral validation: the model's predicted HBM traffic vs the
    # XLA compiler's bytes-accessed across the same schedules (removes
    # wall-clock noise from the comparison)
    import jax
    from repro.core import tpp as _tpp

    def compile_bytes(spec, loops_):
        tl = ThreadedLoop(loops_, spec, reduction_letters=("a",))

        def body(ind, C):
            ik, im, inn = ind
            a = jax.lax.dynamic_slice(A, (im, ik, 0, 0), (1, k_step, bm, bk))[0]
            b = jax.lax.dynamic_slice(B, (inn, ik, 0, 0), (1, k_step, bk, bn))[0]
            acc = _tpp.brgemm(a, b)
            prev = jax.lax.dynamic_slice(C, (inn, im, 0, 0), (1, 1, bm, bn))[0, 0]
            c2 = jnp.where(ik == 0, acc, prev + acc)
            return jax.lax.dynamic_update_slice(C, c2[None, None],
                                                (inn, im, 0, 0))

        f = jax.jit(lambda: tl(body, carry=jnp.zeros((nb, mb, bm, bn),
                                                     jnp.float32)))
        return f.lower().compile().cost_analysis()["bytes accessed"]

    xla_bytes = np.array([compile_bytes(c.spec_string, c.loops)
                          for c in cands[:12]])
    model_bytes = []
    for c in cands[:12]:
        tl = ThreadedLoop(c.loops, c.spec_string, reduction_letters=("a",))
        rep = perf_model.predict(
            tl.nest, in_maps, out_map, dtype=np.float32,
            flops_per_body=2 * bm * bk * bn * k_step,
            tile_mnk=(bm, bn, bk), reduction_letters=("a",))
        model_bytes.append(rep.hbm_bytes)
    model_bytes = np.array(model_bytes)
    rb = np.corrcoef(np.argsort(np.argsort(xla_bytes)),
                     np.argsort(np.argsort(model_bytes)))[0, 1]
    rows.append(("perfmodel_bytes_rank_corr_vs_xla", 0.0,
                 f"spearman={rb:.3f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
