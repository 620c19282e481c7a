"""Paper Fig. 7 / Table II — ResNet-50 convolution layers via PARLOOPER+BRGEMM.

CPU-measured: the Listing-4 conv (PARLOOPER executor, XLA-compiled) vs
jax.lax's direct convolution, on representative ResNet-50 shapes (minibatch
scaled to CPU)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.conv import block_conv_tensors, conv2d_parlooper
from repro.launch.compile_cache import enable_compile_cache

# (H, W, C, K, R, S, stride) — representative RN50 layers, N scaled to 2
LAYERS = [
    (28, 28, 32, 32, 1, 1, 1),
    (28, 28, 32, 32, 3, 3, 1),
    (14, 14, 64, 64, 3, 3, 1),
]


def run():
    rows = []
    rng = np.random.default_rng(0)
    n = 2
    for (h, w, c, kk, r, s, st) in LAYERS:
        x = jnp.asarray(rng.normal(size=(n, h + r - 1, w + s - 1, c)).astype(np.float32))
        wt = jnp.asarray(rng.normal(size=(r, s, c, kk)).astype(np.float32))
        xb, wb = block_conv_tensors(x, wt, min(16, c), min(16, kk))

        ours = jax.jit(lambda xb, wb: conv2d_parlooper(xb, wb, stride=st))
        ours(xb, wb)[0].block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            ours(xb, wb)[0].block_until_ready()
        t1 = (time.perf_counter() - t0) / 5

        lax_f = jax.jit(lambda x, wt: ref.conv2d_ref(x, wt, stride=st))
        lax_f(x, wt).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            lax_f(x, wt).block_until_ready()
        t2 = (time.perf_counter() - t0) / 5
        gflop = 2 * n * h * w * c * kk * r * s / st / st / 1e9
        rows.append((f"conv_{h}x{w}x{c}x{kk}_{r}x{s}", t1 * 1e6,
                     f"gflops={gflop/t1:.1f};lax_ratio={t2/t1:.2f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
