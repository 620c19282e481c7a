"""Flash attention (prefill/train alias + single-token decode kernel).

The prefill/train kernel is no longer bespoke: ``flash_attention_pallas`` is
a thin alias over the *derived* chained-root attention TppGraph
(``fusion.library.fused_attention_graph``) — online softmax lives in the
fusion IR as the ``softmax_online`` reducer + chained contraction, so the
attention kernel is autotuned, differentiated, linted, and profiled like
every other graph.  The original hand-written kernel is kept as
``_legacy_flash_attention_pallas`` purely as a benchmark / parity oracle
(``benchmarks/bench_fusion.py`` races the derived graph against it).

Decode kernel (still bespoke — single-token decode is a gather-shaped
problem, not a GEMM-shaped graph): one query token against a KV cache,
online softmax over KV blocks, per-batch valid-length masking.  (On real TPU
one would pack ≥8 query rows per tile; the logic is identical and
interpret-mode validated here.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas", "flash_decode_pallas"]

_NEG_INF = -1e30


def flash_attention_pallas(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """q (B,H,Sq,D); k/v (B,Hk,Skv,D); H % Hk == 0; Sq == Skv for causal.

    Thin alias over the derived chained-root attention graph (see the module
    docstring).  ``block_q``/``block_kv`` are accepted for signature
    compatibility and ignored — the fusion autotuner owns the tiling now."""
    del block_q, block_kv
    from repro.fusion.library import fused_attention_apply
    return fused_attention_apply(
        q, k, v, causal=causal, window=window, scale=scale,
        out_dtype=out_dtype, vjp=False,
        backend="pallas_interpret" if interpret else "pallas")


def _legacy_flash_attention_pallas(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """The retired hand-written flash kernel — benchmark/parity oracle only.

    q (B,H,Sq,D); k/v (B,Hk,Skv,D); H % Hk == 0; Sq == Skv for causal."""
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    g = h // hk
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    out_dtype = out_dtype or q.dtype
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    nq, nkv = sq // block_q, skv // block_kv
    off = skv - sq  # end-alignment for decode-style prefixes

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        i = pl.program_id(2)
        j = pl.program_id(3)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q_start = i * block_q + off
        kv_start = j * block_kv
        # Block-level skip: block is live unless wholly masked.
        live = jnp.bool_(True)
        if causal:
            live = jnp.logical_and(live, kv_start <= q_start + block_q - 1)
        if window is not None:
            live = jnp.logical_and(
                live, kv_start + block_kv - 1 > q_start - window
            )

        @pl.when(live)
        def _():
            qv = q_ref[0, 0].astype(jnp.float32)
            kv = k_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qv, kv, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = jnp.ones(s.shape, jnp.bool_)
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            if window is not None:
                mask = jnp.logical_and(mask, cols > rows - window)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_ref[:, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(j == nkv - 1)
        def _():
            l = jnp.maximum(l_ref[:, :1], 1e-30)
            o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)

    grid = (b, h, nq, nkv)
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )
    return fn(q, k, v)


def flash_decode_pallas(
    q,
    k_cache,
    v_cache,
    *,
    length=None,
    window: int | None = None,
    block_kv: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """Single-token decode: q (B,H,D); caches (B,Hk,S,D); length (B,) valid
    prefix lengths (defaults to full cache)."""
    b, h, d = q.shape
    _, hk, s, _ = k_cache.shape
    g = h // hk
    out_dtype = out_dtype or q.dtype
    block_kv = min(block_kv, s)
    assert s % block_kv == 0
    nkv = s // block_kv
    scale = 1.0 / np.sqrt(d)
    if length is None:
        length = jnp.full((b,), s, jnp.int32)

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        b_ = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        valid_len = len_ref[b_]
        kv_start = j * block_kv
        live = kv_start < valid_len
        if window is not None:
            live = jnp.logical_and(live, kv_start + block_kv > valid_len - window)

        @pl.when(live)
        def _():
            qv = q_ref[0, 0].astype(jnp.float32)          # (1, D) row
            kv = k_ref[0, 0].astype(jnp.float32)          # (block_kv, D)
            srow = jax.lax.dot_general(
                qv, kv, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                      # (1, block_kv)
            cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, srow.shape, 1)
            mask = cols < valid_len
            if window is not None:
                mask = jnp.logical_and(mask, cols >= valid_len - window)
            srow = jnp.where(mask, srow, _NEG_INF)
            m_prev = m_ref[:1, :1]
            m_new = jnp.maximum(m_prev, jnp.max(srow, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(srow - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(j == nkv - 1)
        def _():
            l = jnp.maximum(l_ref[:1, :1], 1e-30)
            o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)

    grid = (b, h, nkv)
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # length, whole (B,) in SMEM
            pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, j: (b_, h_ // g, j, 0)),
        ],
        # the output carries the same singleton query axis as ``q``, so the
        # block's last two dims equal the array's (Mosaic's tiling rule)
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )
    out = fn(length.astype(jnp.int32), q[:, :, None, :], k_cache, v_cache)
    return out[:, :, 0, :]
