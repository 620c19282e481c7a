"""Block-sparse inference (paper §IV-B / Fig. 10): magnitude-prune an MLP's
weights block-wise to a target sparsity (the paper's 80%, 8×8 blocks), run it
through the Block-SpMM path, and report exactness + speedup vs dense.

Run:  PYTHONPATH=src python examples/sparse_inference.py --sparsity 0.8
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.block_spmm import densify_to_bcsr
from repro.launch.compile_cache import enable_compile_cache


def block_prune(w, sparsity, bs=8):
    """Magnitude-based block pruning (the paper's block-wise weight pruning)."""
    m, n = w.shape
    tiles = w.reshape(m // bs, bs, n // bs, bs).transpose(0, 2, 1, 3)
    scores = np.abs(tiles).sum((2, 3))
    k = int(scores.size * sparsity)
    thresh = np.partition(scores.ravel(), k)[k] if k else -np.inf
    tiles = tiles.copy()
    tiles[scores < thresh] = 0
    return tiles.transpose(0, 2, 1, 3).reshape(m, n)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--ff", type=int, default=2048)
    ap.add_argument("--tokens", type=int, default=256)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    w = rng.normal(size=(args.ff, args.d)).astype(np.float32)  # (out, in)
    w_sp = block_prune(w, args.sparsity)
    actual = 1 - (np.abs(w_sp.reshape(args.ff // 8, 8, args.d // 8, 8)
                         ).sum((1, 3)) != 0).mean()
    blocks, rid, cid = densify_to_bcsr(w_sp, 8, 8)
    x = jnp.asarray(rng.normal(size=(args.tokens, args.d)).astype(np.float32))

    dense = jax.jit(lambda x: x @ jnp.asarray(w_sp).T)
    sparse = jax.jit(lambda x: ref.block_spmm_ref(
        blocks, rid, cid, x.T, nrows_b=args.ff // 8).T)
    yd = dense(x).block_until_ready()
    ys = sparse(x).block_until_ready()
    err = float(jnp.max(jnp.abs(yd - ys)))

    t0 = time.perf_counter()
    for _ in range(20):
        dense(x).block_until_ready()
    td = (time.perf_counter() - t0) / 20
    t0 = time.perf_counter()
    for _ in range(20):
        sparse(x).block_until_ready()
    ts = (time.perf_counter() - t0) / 20

    # apples-to-apples baseline: the SAME work-list path at 0% sparsity
    blocks0, rid0, cid0 = densify_to_bcsr(w, 8, 8)
    sparse0 = jax.jit(lambda x: ref.block_spmm_ref(
        blocks0, rid0, cid0, x.T, nrows_b=args.ff // 8).T)
    sparse0(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        sparse0(x).block_until_ready()
    t0pct = (time.perf_counter() - t0) / 20

    print(f"block sparsity: requested {args.sparsity:.0%}, actual {actual:.0%} "
          f"({blocks.shape[0]} nonzero 8x8 blocks)")
    print(f"exactness vs dense: max err {err:.2e}")
    print(f"XLA dense matmul    {td*1e6:8.0f} us  (vendor-library analogue)")
    print(f"work-list @ 0%      {t0pct*1e6:8.0f} us")
    print(f"work-list @ {actual:.0%}     {ts*1e6:8.0f} us   "
          f"kernel-level speedup {t0pct/ts:.2f}x "
          f"(ideal {1/(1-args.sparsity):.2f}x; TPU Pallas kernel skips "
          f"zero blocks identically)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
