"""Paper Listing 6 — the fused Bert-Output/Bert-SelfOutput layer.

The paper's showcase TPP fusion: a BRGEMM over blocked tensors with bias,
dropout, residual-add and the layernorm *equation* fused at small 2D-block
granularity, "to maximize the out-of-cache-reuse of tensors among subsequent
operators" (§IV-A).  TPU adaptation: the same fusion holds the output block
in VMEM across the epilogue TPPs; because layernorm normalizes over the full
feature dim, the N (feature) loop must be the innermost band so a row-block's
statistics are complete when the last N tile finishes — we therefore schedule
grid = (M tiles, K tiles, N inner) with an fp32 row-accumulator strip for the
(sum, sum-of-squares) statistics, and apply the layernorm equation on the
stored row panel in the last grid step.

Layout: x (M, K) @ w (K, N) + bias (N), + residual (M, N), dropout with a
counter-based mask (pre-generated bits — TPU PRNG in-kernel is a further
step), layernorm(gamma, beta) over N.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["fused_output_pallas", "fused_output_ref"]


def fused_output_ref(x, w, bias, residual, gamma, beta, *, keep_mask=None,
                     dropout_rate: float = 0.0, eps: float = 1e-5,
                     out_dtype=None):
    """Pure-jnp oracle of Listing 6."""
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    acc = acc + bias.astype(jnp.float32)
    if keep_mask is not None and dropout_rate > 0.0:
        acc = jnp.where(keep_mask, acc / (1.0 - dropout_rate), 0.0)
    acc = acc + residual.astype(jnp.float32)
    mu = acc.mean(-1, keepdims=True)
    var = ((acc - mu) ** 2).mean(-1, keepdims=True)
    y = (acc - mu) * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(out_dtype or x.dtype)


def fused_output_pallas(x, w, bias, residual, gamma, beta, *, keep_mask=None,
                        dropout_rate: float = 0.0, eps: float = 1e-5,
                        bm: int = 32, bk: int = 64, bn: int = 128,
                        out_dtype=None, interpret: bool = False):
    """x (M,K) @ w (K,N) +bias → dropout → +residual → layernorm, fused.

    Grid (M/bm, K/bk, N/bn): K above N so the reduction finishes per N tile;
    the (bm, N) fp32 row panel lives in VMEM scratch, statistics accumulate
    per N tile, and the normalized panel is flushed once per M block."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and k % bk == 0 and n % bn == 0
    out_dtype = out_dtype or x.dtype
    nk, nn = k // bk, n // bn
    if keep_mask is None:
        keep_mask = jnp.ones((m, n), jnp.bool_)
    scale = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else 1.0

    def kernel(x_ref, w_ref, b_ref, r_ref, g_ref, bet_ref, mask_ref,
               o_ref, panel_ref, stats_ref, acc_ref):
        j = pl.program_id(1)   # N tile
        ik = pl.program_id(2)  # K step (innermost: reduction completes per tile)

        @pl.when(jnp.logical_and(ik == 0, j == 0))
        def _():
            stats_ref[...] = jnp.zeros_like(stats_ref)

        @pl.when(ik == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        # epilogue for this N tile once its K reduction is complete
        @pl.when(ik == nk - 1)
        def _():
            v = acc_ref[...] + b_ref[0].astype(jnp.float32)
            if dropout_rate > 0.0:
                v = jnp.where(mask_ref[...], v * scale, 0.0)
            v = v + r_ref[...].astype(jnp.float32)
            panel_ref[:, pl.ds(j * bn, bn)] = v
            stats_ref[:, 0] += jnp.sum(v, axis=1)
            stats_ref[:, 1] += jnp.sum(v * v, axis=1)

            # last N tile: layernorm equation over the finished row panel
            @pl.when(j == nn - 1)
            def _():
                s1 = stats_ref[:, 0:1]
                s2 = stats_ref[:, 1:2]
                mu = s1 / n
                var = s2 / n - mu * mu
                rstd = jax.lax.rsqrt(var + eps)
                y = (panel_ref[...] - mu) * rstd
                y = (y * g_ref[0].astype(jnp.float32)
                     + bet_ref[0].astype(jnp.float32))
                o_ref[...] = y.astype(o_ref.dtype)

    fn = pl.pallas_call(
        kernel,
        grid=(m // bm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ik: (i, ik)),
            pl.BlockSpec((bk, bn), lambda i, j, ik: (ik, j)),
            pl.BlockSpec((1, bn), lambda i, j, ik: (0, j)),
            pl.BlockSpec((bm, bn), lambda i, j, ik: (i, j)),
            pl.BlockSpec((1, n), lambda i, j, ik: (0, 0)),
            pl.BlockSpec((1, n), lambda i, j, ik: (0, 0)),
            pl.BlockSpec((bm, bn), lambda i, j, ik: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i, j, ik: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, n), jnp.float32),    # finished row panel
            pltpu.VMEM((bm, 2), jnp.float32),    # (sum, sum-sq) strip
            pltpu.VMEM((bm, bn), jnp.float32),   # K accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )
    return fn(x, w, bias.reshape(1, n), residual, gamma.reshape(1, n),
              beta.reshape(1, n), keep_mask)
