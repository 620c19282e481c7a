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
"""
from __future__ import annotations

import contextlib
import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

__all__ = [
    "current_backend", "use_backend",
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


def paged_decode_attention(q, k_pool, v_pool, page_table, *, page_size,
                           length, window=None, out_dtype=None, backend=None,
                           block_kv=128):
    """Decode attention over token-major page pools (P, page_size, Hk·D)
    indexed by ``page_table`` (B, maxp) — the serving engine's KV layout.

    The XLA path runs the gather in pool layout (no transpose copy); Pallas
    backends gather the per-slot view to the head-major cache layout and
    reuse ``flash_decode_pallas`` (the gather is the price of not carrying a
    dedicated paged kernel per backend)."""
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.paged_decode_attention_ref(
            q, k_pool, v_pool, page_table, page_size=page_size,
            length=length, window=window, out_dtype=out_dtype)
    from repro.kernels.flash_attention import flash_decode_pallas
    b, maxp = page_table.shape
    s, hk = maxp * page_size, k_pool.shape[2] // q.shape[-1]
    # (B, maxp, ps, Hk·D) → (B, Hk, maxp·ps, D)
    k = jnp.swapaxes(k_pool[page_table].reshape(b, s, hk, -1), 1, 2)
    v = jnp.swapaxes(v_pool[page_table].reshape(b, s, hk, -1), 1, 2)
    return flash_decode_pallas(
        q, k, v, length=length, window=window, block_kv=block_kv,
        out_dtype=out_dtype, interpret=_interp(backend),
    )


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


def grouped_matmul(x, group_id, w, *, bf=128, out_dtype=None, backend=None):
    backend = backend or current_backend()
    if backend == "xla":
        return _ref.grouped_matmul_ref(x, group_id, w, out_dtype=out_dtype)
    from repro.kernels.block_spmm import grouped_matmul_pallas
    return grouped_matmul_pallas(
        x, group_id, w, bf=bf, out_dtype=out_dtype, interpret=_interp(backend),
    )


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
