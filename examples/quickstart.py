"""Quickstart — the paper's Listing 1, in this framework.

Declares the GEMM's logical loops with PARLOOPER, expresses the computation
with TPPs, then shows the three instantiation targets of one and the same
loop_spec_string knob:
  1. the pure-JAX executor (the paper's JITed C++ nest),
  2. the Pallas TPU schedule (grid/BlockSpec; validated in interpret mode),
  3. the auto-tuner + performance model picking the knob for you.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (LoopSpec, TensorMap, ThreadedLoop, autotune,
                        plan_pallas, tpp)
from repro.kernels.brgemm import matmul_pallas
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# --- problem: C[M,N] = A[M,K] @ B[K,N], blocked by (bm, bk, bn) -----------
M, K, N = 256, 512, 256
bm, bk, bn = 32, 64, 32
Mb, Kb, Nb = M // bm, K // bk, N // bn
rng = np.random.default_rng(0)
A = jnp.asarray(rng.normal(size=(Mb, Kb, bm, bk)).astype(np.float32))
B = jnp.asarray(rng.normal(size=(Nb, Kb, bk, bn)).astype(np.float32))
ref = np.einsum("mkab,nkbc->nmac", np.asarray(A), np.asarray(B))

# --- Listing 1: declare the logical loops (a=K, b=M, c=N) -----------------
k_step = 2
loops = [
    LoopSpec(0, Kb, k_step, name="K"),
    LoopSpec(0, Mb, 1, block_steps=(4, 2), name="M"),   # b appears 3× in the knob
    LoopSpec(0, Nb, 1, block_steps=(4,), name="N"),     # c appears 2×
]
spec_string = "bcaBCb"  # the single runtime knob (paper Listing 2)
gemm_loop = ThreadedLoop(loops, spec_string, reduction_letters=("a",))
print("generated nest for", spec_string)
print(gemm_loop.describe(), "\n")


# --- the body: zero TPP + BRGEMM TPP over logical indices (Listing 1) -----
def body(ind, C):
    ik, im, inn = ind
    a = jax.lax.dynamic_slice(A, (im, ik, 0, 0), (1, k_step, bm, bk))[0]
    b = jax.lax.dynamic_slice(B, (inn, ik, 0, 0), (1, k_step, bk, bn))[0]
    acc = tpp.brgemm(a, b)                       # batch-reduce GEMM TPP
    prev = jax.lax.dynamic_slice(C, (inn, im, 0, 0), (1, 1, bm, bn))[0, 0]
    c2 = jnp.where(ik == 0, acc, prev + acc)     # zero TPP on first K visit
    return jax.lax.dynamic_update_slice(C, c2[None, None], (inn, im, 0, 0))


C = gemm_loop(body, carry=jnp.zeros((Nb, Mb, bm, bn), jnp.float32))
print("executor max err:", float(np.abs(np.asarray(C) - ref).max()))

# --- the same knob lowered onto a Pallas grid/BlockSpec schedule ----------
a_flat = np.asarray(A).transpose(0, 2, 1, 3).reshape(M, K)
b_flat = np.asarray(B).transpose(1, 2, 0, 3).reshape(K, N)
out = matmul_pallas(jnp.asarray(a_flat), jnp.asarray(b_flat),
                    spec_string="bca", tiles=(bm, bk, bn), interpret=True)
want = a_flat @ b_flat
print("pallas (interpret) max err:", float(np.abs(np.asarray(out) - want).max()))

# --- auto-tune the knob (paper §II-D/E) -----------------------------------
in_maps = [TensorMap(("b", "a"), (bm, bk)), TensorMap(("c", "a"), (bk, bn))]
out_map = TensorMap(("c", "b"), (bm, bn))
t0 = time.perf_counter()
results = autotune.autotune(
    loops, in_maps, out_map, dtype=jnp.bfloat16,
    flops_per_body=2 * bm * bk * bn * k_step, tile_mnk=(bm, bn, bk),
    reduction_letters=("a",), parallel_letters=("b", "c"),
    max_candidates=200)
print(f"\nauto-tuned {len(results)} loop_spec_strings in "
      f"{time.perf_counter()-t0:.2f}s; top 5:")
for r in results[:5]:
    print(f"  {r.candidate.spec_string:24s} predicted {r.score:8.0f} GFLOP/s "
          f"({r.report.bound}-bound)")
