"""Chunked selective-scan Pallas kernel (mamba1 recurrence).

The paper has no scan TPP — this is a documented extension (DESIGN.md §4):
the falcon-mamba / jamba architectures make the selective scan a first-order
compute hot-spot, so it gets the same treatment as the contractions.

TPU adaptation: the recurrence is sequential in time but dense in
(d_inner × d_state), so the kernel keeps the running state h (D, N) resident
in fp32 VMEM scratch across the chunk grid dimension (grid = (B, L/chunk),
chunk dim ``arbitrary`` → sequential, state survives between grid steps) and
walks the chunk with an in-kernel ``fori_loop`` of VPU outer-product updates.
HBM traffic is therefore one read of (x, dt, B, C) and one write of y per
token — the operational-intensity optimum for this op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["mamba_scan_pallas"]


def mamba_scan_pallas(
    x,
    dt,
    a,
    b_in,
    c_in,
    d_skip,
    *,
    h0=None,
    chunk: int = 64,
    out_dtype=None,
    interpret: bool = False,
):
    """x, dt: (B, L, D); a: (D, N); b_in, c_in: (B, L, N); d_skip: (D,).

    Returns (y (B, L, D), h_final (B, D, N) fp32)."""
    bsz, l, dch = x.shape
    n = a.shape[1]
    out_dtype = out_dtype or x.dtype
    chunk = min(chunk, l)
    assert l % chunk == 0
    nchunks = l // chunk
    if h0 is None:
        h0 = jnp.zeros((bsz, dch, n), jnp.float32)

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
               y_ref, hout_ref, h_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            h_ref[...] = h0_ref[0]

        av = a_ref[...].astype(jnp.float32)          # (D, N)
        dv = d_ref[0].astype(jnp.float32)            # (D,)

        def step(t, _):
            xt = x_ref[0, t].astype(jnp.float32)     # (D,)
            dtt = dt_ref[0, t].astype(jnp.float32)   # (D,)
            bt = b_ref[0, t].astype(jnp.float32)     # (N,)
            ct = c_ref[0, t].astype(jnp.float32)     # (N,)
            da = jnp.exp(dtt[:, None] * av)          # (D, N)
            h = h_ref[...] * da + (dtt * xt)[:, None] * bt[None, :]
            h_ref[...] = h
            y = jnp.sum(h * ct[None, :], axis=1) + dv * xt
            y_ref[0, t] = y.astype(y_ref.dtype)
            return 0

        jax.lax.fori_loop(0, chunk, step, 0)

        @pl.when(c == nchunks - 1)
        def _():
            hout_ref[0] = h_ref[...]

    grid = (bsz, nchunks)
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dch), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dch), lambda b, c: (b, c, 0)),
            pl.BlockSpec((dch, n), lambda b, c: (0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dch), lambda b, c: (0, 0)),
            pl.BlockSpec((1, dch, n), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dch), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dch, n), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, dch), out_dtype),
            jax.ShapeDtypeStruct((bsz, dch, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dch, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )
    return fn(x, dt, a, b_in, c_in, d_skip.reshape(1, dch), h0)
