"""Paper Fig. 3 — MLP with fused Bias+ReLU epilogues.

Measures (CPU wall + HLO cost analysis) the fused BRGEMM+bias+ReLU TPP layer
against the unfused 3-op version: the derived columns are wall-time ratio and
HBM bytes-accessed ratio (the fusion's memory saving is platform-independent).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tpp
from repro.launch.compile_cache import enable_compile_cache


def _fused(x, w, b):
    return tpp.relu(tpp.bias_add(
        jnp.dot(x, w, preferred_element_type=jnp.float32), b)).astype(x.dtype)


def _unfused_steps(x, w, b):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    y = (y.astype(jnp.float32) + b).astype(x.dtype)
    return jnp.maximum(y, 0)


def run():
    rows = []
    rng = np.random.default_rng(0)
    n = 512  # paper's minibatch
    for (m, k) in [(512, 512), (1024, 1024), (2048, 2048)]:
        x = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(m,)).astype(np.float32))

        f1 = jax.jit(_fused)
        co1 = f1.lower(x, w, b).compile()
        f2 = jax.jit(_unfused_steps)
        co2 = f2.lower(x, w, b).compile()
        by1 = co1.cost_analysis()["bytes accessed"]
        by2 = co2.cost_analysis()["bytes accessed"]

        f1(x, w, b).block_until_ready()
        f2(x, w, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            f1(x, w, b).block_until_ready()
        t1 = (time.perf_counter() - t0) / 10
        t0 = time.perf_counter()
        for _ in range(10):
            f2(x, w, b).block_until_ready()
        t2 = (time.perf_counter() - t0) / 10
        rows.append((f"mlp_fused_{m}x{k}", t1 * 1e6,
                     f"wall_ratio={t2/t1:.2f};bytes_ratio={by2/by1:.2f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
