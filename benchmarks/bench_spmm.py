"""Paper Fig. 8 — BF16 Block-SpMM sparsity sweep (M=N=K scaled to CPU).

Measured: XLA block-SpMM wall time vs the dense GEMM baseline across sparsity
levels.  Derived: speedup per sparsity + the paper's block-size argument
reproduced analytically — MXU accumulation-depth efficiency per block size
(the 4×4-blocks-cap-at-12.5%-of-peak systolic effect, adapted from AMX to the
128-deep MXU)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model
from repro.kernels import ref
from repro.kernels.block_spmm import densify_to_bcsr
from repro.launch.compile_cache import enable_compile_cache


def run():
    rows = []
    rng = np.random.default_rng(0)
    m = k = n = 512
    bm = bk = 16
    dense_w = rng.normal(size=(m, k)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))

    # baseline = the same work-list path at 0% sparsity (apples-to-apples;
    # the XLA scatter path is not the TPU kernel, so relative speedups are
    # the meaningful CPU-measurable quantity)
    blocks0, rid0, cid0 = densify_to_bcsr(dense_w, bm, bk)
    base_f = jax.jit(lambda bl, r, c, xx: ref.block_spmm_ref(
        bl, r, c, xx, nrows_b=m // bm))
    base_f(blocks0, rid0, cid0, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        base_f(blocks0, rid0, cid0, x).block_until_ready()
    t_dense = (time.perf_counter() - t0) / 10

    for sparsity in (0.0, 0.5, 0.7, 0.9):
        tiles = dense_w.reshape(m // bm, bm, k // bk, bk).transpose(0, 2, 1, 3).copy()
        mask = rng.random((m // bm, k // bk)) < sparsity
        tiles[mask] = 0
        w_sp = tiles.transpose(0, 2, 1, 3).reshape(m, k)
        blocks, rid, cid = densify_to_bcsr(w_sp, bm, bk)
        f = jax.jit(lambda bl, r, c, xx: ref.block_spmm_ref(
            bl, r, c, xx, nrows_b=m // bm))
        f(blocks, rid, cid, x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            f(blocks, rid, cid, x).block_until_ready()
        t_sp = (time.perf_counter() - t0) / 10
        rows.append((f"spmm_sparsity_{sparsity:.1f}", t_sp * 1e6,
                     f"speedup_vs_dense={t_dense/t_sp:.2f};nnzb={blocks.shape[0]}"))

    # block-size systolic-efficiency argument (paper: 4×4 caps at 12.5% AMX)
    for bs in (4, 8, 16, 32):
        eff = perf_model.mxu_efficiency(bs, 128, bs)
        rows.append((f"spmm_blocksize_{bs}x{bs}_mxu_eff", 0.0,
                     f"eff={eff:.3f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
