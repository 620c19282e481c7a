"""Public jit'd kernel API with backend dispatch.

``backend`` values:
  * ``"xla"``               — pure-jnp reference path (``ref.py``).  Default on
                              CPU and in the multi-pod dry-run: Pallas TPU
                              kernels cannot lower for the CPU backend, and the
                              dry-run's cost analysis must reflect lowered HLO.
  * ``"pallas_interpret"``  — the Pallas kernels, interpret mode (CPU
                              correctness validation; what the tests sweep).
  * ``"pallas"``            — the Pallas kernels compiled for real TPU (the
                              production target).

Select globally via env ``REPRO_KERNEL_BACKEND``, per-call via ``backend=``,
or with the ``use_backend`` context manager.

One entry point leaves ``"xla"`` on a TPU: ``paged_decode_attention`` lowers
to its Pallas kernel there under ``"xla"`` and ``"pallas"`` alike, and to
the reference elsewhere (``lax.platform_dependent``).  No XLA formulation of
paged decode reads only the live pages: each gathers every slot's whole
padded view first, which makes it the largest share of a decode step.

Every entry point runs under ``jax.named_scope("ops.<function>")``, so its
operations carry that name in a profiler trace whatever backend lowers them.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

__all__ = [
    "current_backend", "use_backend", "scoped",
    "matmul", "attention", "decode_attention", "paged_decode_attention",
    "mamba_scan",
    "block_spmm", "grouped_matmul", "conv2d",
]

_BACKEND_OVERRIDE: list[str] = []


def current_backend() -> str:
    if _BACKEND_OVERRIDE:
        return _BACKEND_OVERRIDE[-1]
    return os.environ.get("REPRO_KERNEL_BACKEND", "xla")


@contextlib.contextmanager
def use_backend(name: str):
    assert name in ("xla", "pallas", "pallas_interpret"), name
    _BACKEND_OVERRIDE.append(name)
    try:
        yield
    finally:
        _BACKEND_OVERRIDE.pop()


def _interp(backend):
    return backend == "pallas_interpret"


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``, a new
    scope object per call (one shared ``named_scope`` object keeps its
    caller's name stack on itself, so it is not re-entrant)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


@scoped("ops.matmul")
def matmul(a, b, *, bias=None, activation=None, out_dtype=None,
           spec_string=None, tiles=None, backend=None):
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.matmul_ref(a, b, bias=bias, activation=activation,
                               out_dtype=out_dtype)
    from repro.kernels.brgemm import DEFAULT_SPEC, matmul_pallas
    return matmul_pallas(
        a, b, bias=bias, activation=activation, out_dtype=out_dtype,
        spec_string=spec_string or DEFAULT_SPEC, tiles=tiles,
        interpret=_interp(backend),
    )


@scoped("ops.attention")
def attention(q, k, v, *, causal=True, window=None, scale=None,
              out_dtype=None, backend=None, block_q=128, block_kv=128):
    backend = backend or current_backend()
    if backend == "xla":
        # memory-bounded chunked path once the score matrix would be large
        if q.shape[2] * k.shape[2] > 512 * 1024 and q.shape[2] > 512:
            return _ref.attention_xla_chunked(
                q, k, v, causal=causal, window=window, scale=scale,
                out_dtype=out_dtype)
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale, out_dtype=out_dtype)
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, out_dtype=out_dtype,
        interpret=_interp(backend),
    )


@scoped("ops.decode_attention")
def decode_attention(q, k_cache, v_cache, *, length=None, window=None,
                     out_dtype=None, backend=None, block_kv=128):
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.decode_attention_ref(q, k_cache, v_cache, length=length,
                                         window=window, out_dtype=out_dtype)
    from repro.kernels.flash_attention import flash_decode_pallas
    return flash_decode_pallas(
        q, k_cache, v_cache, length=length, window=window, block_kv=block_kv,
        out_dtype=out_dtype, interpret=_interp(backend),
    )


@scoped("ops.paged_decode_attention")
def paged_decode_attention(q, k_pool, v_pool, page_table, *, page_size,
                           length, layer=0, window=None, out_dtype=None,
                           backend=None):
    """Decode attention over token-major page pools (L, P, page_size, Hk·D),
    stacked over a layer group, read at ``layer`` (int or int32 scalar) —
    or one layer's (P, page_size, Hk·D) — and indexed by ``page_table``
    (B, maxp): the serving engine's KV layout.

    On a TPU this is ``paged_decode_pallas`` whatever the backend says:
    the kernel reads each slot's live pages in place, where any XLA
    formulation gathers the whole padded view of every slot first.  The
    choice is made when the program lowers (``lax.platform_dependent``),
    so CPU programs keep the reference; ``pallas_interpret`` runs the
    kernel in interpret mode."""
    from repro.kernels.paged_attention import paged_decode_pallas
    kw = dict(page_size=page_size, window=window, out_dtype=out_dtype)
    layer = jnp.asarray(layer, jnp.int32)
    if (backend or current_backend()) == "pallas_interpret":
        return paged_decode_pallas(q, k_pool, v_pool, page_table,
                                   length=length, layer=layer,
                                   interpret=True, **kw)

    def kernel(q, k_pool, v_pool, page_table, length, layer):
        return paged_decode_pallas(q, k_pool, v_pool, page_table,
                                   length=length, layer=layer, **kw)

    def reference(q, k_pool, v_pool, page_table, length, layer):
        return _ref.paged_decode_attention_ref(q, k_pool, v_pool, page_table,
                                               length=length, layer=layer,
                                               **kw)

    return jax.lax.platform_dependent(q, k_pool, v_pool, page_table, length,
                                      layer, tpu=kernel, default=reference)


@scoped("ops.mamba_scan")
def mamba_scan(x, dt, a, b_in, c_in, d_skip, *, h0=None, out_dtype=None,
               backend=None, chunk=64):
    backend = backend or current_backend()
    if backend == "xla":
        if x.shape[1] > 64:  # chunked path bounds backward residuals
            return _ref.mamba_scan_xla_chunked(
                x, dt, a, b_in, c_in, d_skip, h0=h0, chunk=chunk,
                out_dtype=out_dtype)
        return _ref.mamba_scan_ref(x, dt, a, b_in, c_in, d_skip, h0=h0,
                                   out_dtype=out_dtype)
    from repro.kernels.mamba_scan import mamba_scan_pallas
    return mamba_scan_pallas(
        x, dt, a, b_in, c_in, d_skip, h0=h0, chunk=chunk,
        out_dtype=out_dtype, interpret=_interp(backend),
    )


@scoped("ops.block_spmm")
def block_spmm(blocks, row_id, col_id, b, *, nrows_b, bn=128,
               out_dtype=None, backend=None):
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.block_spmm_ref(blocks, row_id, col_id, b,
                                   nrows_b=nrows_b, out_dtype=out_dtype)
    from repro.kernels.block_spmm import block_spmm_pallas
    return block_spmm_pallas(
        blocks, row_id, col_id, b, nrows_b=nrows_b, bn=bn,
        out_dtype=out_dtype, interpret=_interp(backend),
    )


@scoped("ops.grouped_matmul")
def grouped_matmul(x, w, group_sizes, *, out_dtype=None):
    """Rows sorted by group times each group's matrix: x (M, K), w (G, K, N),
    ``group_sizes`` (G,) int32 → (M, N), row block g through ``w[g]``;
    rows past ``sum(group_sizes)`` belong to no group and come out zero.
    One path whatever the backend: ``lax.ragged_dot``, which XLA lowers
    natively on a TPU (the MoE layer's dropless expert product)."""
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=out_dtype)


@scoped("ops.conv2d")
def conv2d(x_nhwc, w_rsck, *, stride=1, out_dtype=None, backend=None):
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.conv2d_ref(x_nhwc, w_rsck, stride=stride,
                               out_dtype=out_dtype)
    from repro.kernels.conv import (block_conv_tensors, conv2d_1x1_pallas,
                                    conv2d_parlooper)
    r, s = w_rsck.shape[:2]
    bc = min(32, x_nhwc.shape[-1])
    bk = min(32, w_rsck.shape[-1])
    xb, wb = block_conv_tensors(x_nhwc, w_rsck, bc, bk)
    if r == 1 and s == 1:
        ob = conv2d_1x1_pallas(xb, wb, stride=stride, out_dtype=out_dtype,
                               interpret=_interp(backend))
    else:
        ob = conv2d_parlooper(xb, wb, stride=stride, out_dtype=out_dtype)
    n, kb, p, q, bko = ob.shape
    return ob.transpose(0, 2, 3, 1, 4).reshape(n, p, q, kb * bko)
