"""Paper Figs. 2/4/5 — GEMM with PARLOOPER/TPP across shapes.

On this CPU-only container the TPU numbers are *predicted* by the schedule
model (the measured counterpart is Fig. 6's correlation bench); we report per
paper shape: the auto-tuned loop_spec_string, predicted GFLOPS, roofline
fraction of the 197 TF/s bf16 peak, and the tuning cost (the paper's headline:
~1000 schedules in seconds, 2.3–500× faster than TVM's autotuner).
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core import LoopSpec, TensorMap, ThreadedLoop, autotune, perf_model
from repro.core.loops import LegalityError
from repro.core.pallas_lowering import validate_reduction_innermost
from repro.kernels.brgemm import pick_tiles
from repro.launch.compile_cache import enable_compile_cache

# paper Fig. 2 (square / skewed) + Fig. 5 (BERT/GPT/DLRM shapes)
SHAPES = [
    (1024, 1024, 1024), (2048, 2048, 2048), (4096, 4096, 4096),
    (256, 1024, 4096), (1024, 4096, 1024),      # BERT-ish
    (2048, 5120, 5120), (4096, 4096, 11008),    # GPT/Llama-ish
]


def tune_one(m, k, n, dtype=jnp.bfloat16):
    bm, bk, bn = pick_tiles(m, k, n, dtype)
    loops = [LoopSpec(0, k // bk, 1, name="K"),
             LoopSpec(0, m // bm, 1, name="M"),
             LoopSpec(0, n // bn, 1, name="N")]
    in_maps = [TensorMap(("b", "a"), (bm, bk), layout="flat"),
               TensorMap(("a", "c"), (bk, bn), layout="flat")]
    out_map = TensorMap(("b", "c"), (bm, bn), layout="flat")
    t0 = time.perf_counter()
    results = autotune.autotune(
        loops, in_maps, out_map, dtype=dtype,
        flops_per_body=2 * bm * bk * bn, tile_mnk=(bm, bn, bk),
        reduction_letters=("a",), parallel_letters=("b", "c"),
        max_candidates=300)
    dt = time.perf_counter() - t0
    # restrict to Pallas-legal schedules (reduction innermost)
    best = None
    for r in results:
        tl = ThreadedLoop(r.candidate.loops, r.candidate.spec_string,
                          reduction_letters=("a",))
        try:
            validate_reduction_innermost(tl.nest, ("b", "c"), ("a",))
        except LegalityError:
            continue
        best = r
        break
    best = best or results[0]
    return best, len(results), dt


def run():
    rows = []
    for (m, k, n) in SHAPES:
        best, n_cand, dt = tune_one(m, k, n)
        frac = best.report.gflops * 1e9 / 197e12
        rows.append((
            f"gemm_{m}x{k}x{n}", dt * 1e6 / max(n_cand, 1),
            f"best={best.candidate.spec_string};pred_gflops={best.report.gflops:.0f};"
            f"roofline_frac={frac:.2f};bound={best.report.bound};"
            f"cands={n_cand};tune_s={dt:.2f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
