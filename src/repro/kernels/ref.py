"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the interpret-mode kernels are asserted against
(tests sweep shapes/dtypes) and the XLA execution path the models use on
CPU / in the dry-run (``kernel_backend='xla'``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "matmul_ref", "brgemm_blocked_ref", "mlp_ref",
    "block_spmm_ref", "grouped_matmul_ref", "bcsr_to_dense",
    "attention_ref", "decode_attention_ref",
    "mamba_scan_ref", "conv2d_ref",
]


# --------------------------------------------------------------------------
# GEMM family
# --------------------------------------------------------------------------

def matmul_ref(a, b, *, bias=None, activation=None, out_dtype=None):
    out_dtype = out_dtype or a.dtype
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation is not None:
        from repro.core import tpp
        acc = tpp.UNARY_TPPS[activation](acc) if activation in tpp.UNARY_TPPS else acc
    return acc.astype(out_dtype)


def brgemm_blocked_ref(a, b, *, out_dtype=None):
    """Blocked-layout BRGEMM: A (Mb,Kb,bm,bk) × B (Nb,Kb,bk,bn) → C (Nb,Mb,bm,bn)."""
    acc = jnp.einsum(
        "mkab,nkbc->nmac",
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return acc.astype(out_dtype or a.dtype)


def mlp_ref(x, weights, biases, *, activation="gelu", out_dtype=None):
    """Cascading fully-connected layers (paper §III-A)."""
    from repro.core import tpp
    act = tpp.UNARY_TPPS[activation]
    h = x
    for w, b in zip(weights, biases):
        h = jnp.dot(h, w, preferred_element_type=jnp.float32)
        h = h + b.astype(jnp.float32)
        h = act(h).astype(out_dtype or x.dtype)
    return h


# --------------------------------------------------------------------------
# Block-sparse × dense (paper §III-C) and grouped matmul (MoE)
# --------------------------------------------------------------------------

def bcsr_to_dense(blocks, row_id, col_id, nrows_b, ncols_b):
    """Materialize BCSR storage to a dense matrix (numpy, test helper)."""
    blocks = np.asarray(blocks)
    nnzb, bm, bk = blocks.shape
    out = np.zeros((nrows_b * bm, ncols_b * bk), blocks.dtype)
    for t in range(nnzb):
        r, c = int(row_id[t]), int(col_id[t])
        out[r * bm:(r + 1) * bm, c * bk:(c + 1) * bk] += blocks[t]
    return out


def block_spmm_ref(blocks, row_id, col_id, b, *, nrows_b, out_dtype=None):
    """C = A_sparse @ B with A in BCSR work-list form.

    ``blocks`` (nnzb, bm, bk); ``row_id``/``col_id`` (nnzb,) block coords;
    ``b`` (K, N) dense.  Pure-jnp scatter-add oracle.
    """
    nnzb, bm, bk = blocks.shape
    n = b.shape[1]
    # gather B tiles per work item: (nnzb, bk, n)
    b_tiles = b.reshape(-1, bk, n)[col_id]
    partial = jnp.einsum(
        "tab,tbc->tac", blocks.astype(jnp.float32), b_tiles.astype(jnp.float32)
    )
    out = jnp.zeros((nrows_b, bm, n), jnp.float32).at[row_id].add(partial)
    return out.reshape(nrows_b * bm, n).astype(out_dtype or b.dtype)


def grouped_matmul_ref(x, group_id, w, *, out_dtype=None):
    """Per-row-tile expert matmul: x (T, d) row-tiles of size bm with
    ``group_id`` (T//bm,) expert per tile; w (E, d, f)."""
    t_tiles = group_id.shape[0]
    bm = x.shape[0] // t_tiles
    xt = x.reshape(t_tiles, bm, -1)
    wt = w[group_id]  # (T_tiles, d, f)
    out = jnp.einsum("tbd,tdf->tbf", xt.astype(jnp.float32), wt.astype(jnp.float32))
    return out.reshape(x.shape[0], w.shape[-1]).astype(out_dtype or x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal=True, window=None, scale=None,
                  out_dtype=None):
    """Multi-head attention oracle with GQA + causal/sliding-window masking.

    q (B, H, Sq, D); k/v (B, Hk, Skv, D) with H % Hk == 0.
    ``window``: sliding-window size (keys within [i-window+1, i]).
    """
    b, h, sq, d = q.shape
    hk = k.shape[1]
    g = h // hk
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kq = jnp.repeat(k, g, axis=1)
    vq = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kq.astype(jnp.float32))
    s = s * scale
    skv = k.shape[2]
    rows = jnp.arange(sq)[:, None] + (skv - sq)  # align ends (decode-style)
    cols = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (cols > rows - window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32))
    return o.astype(out_dtype or q.dtype)


def decode_attention_ref(q, k_cache, v_cache, *, length=None, window=None,
                         out_dtype=None):
    """Single-token decode oracle: q (B, H, D); caches (B, Hk, S, D);
    ``length`` (B,) valid prefix lengths (None = full); ``window`` sliding
    window (keys within [length-window, length))."""
    b, h, d = q.shape
    hk = k_cache.shape[1]
    g = h // hk
    # GQA-native: no kv `repeat` (would materialize g× the full cache)
    qg = q.reshape(b, hk, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(d)
    if length is not None:
        cols = jnp.arange(k_cache.shape[2])[None, None, None, :]
        mask = cols < length[:, None, None, None]
        if window is not None:
            mask = mask & (cols >= length[:, None, None, None] - window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, d).astype(out_dtype or q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, page_table, *, page_size,
                               length, window=None, out_dtype=None):
    """Single-token decode over a paged KV pool: q (B, H, D); pools are
    token-major page pools (P, page_size, Hk·D) shared by every slot;
    ``page_table`` (B, maxp) int32 names each slot's pages (trash-page
    sentinel in unused entries); ``length`` (B,) valid prefix lengths.

    Token-major pools keep the decode *write* a natural (page, offset) row
    scatter; for the read, the gathered view is swapped to the head-major
    cache layout before the einsum — XLA folds the swap into the gather's
    output layout, whereas contracting the token-major view directly
    strides over heads and scalarizes the dot on CPU (measured ~4× the
    whole attention cost).  Positions beyond ``length`` read reserved /
    trash pages and are masked by :func:`decode_attention_ref`."""
    b, maxp = page_table.shape
    s, hk = maxp * page_size, k_pool.shape[2] // q.shape[-1]
    k = jnp.swapaxes(k_pool[page_table].reshape(b, s, hk, -1), 1, 2)
    v = jnp.swapaxes(v_pool[page_table].reshape(b, s, hk, -1), 1, 2)
    return decode_attention_ref(q, k, v, length=length, window=window,
                                out_dtype=out_dtype)


# --------------------------------------------------------------------------
# Mamba selective scan (mamba1)
# --------------------------------------------------------------------------

def attention_xla_chunked(q, k, v, *, causal=True, window=None, scale=None,
                          block_q: int = 256, out_dtype=None):
    """Memory-bounded attention for the XLA path: scan over query blocks with
    a checkpointed body, so only one (B, Hk, g, bq, Skv) score block is ever
    live and the backward recomputes it (the flash-attention memory property,
    expressed in pure lax — this is what the dry-run lowers; the Pallas flash
    kernel is the TPU runtime fast path).  GQA handled by grouping query
    heads — no kv ``repeat`` (keeps kv-head sharding propagation intact and
    avoids the g× copy)."""
    b, h, sq, d = q.shape
    hk, skv = k.shape[1], k.shape[2]
    vd = v.shape[-1]  # may differ from d (MLA: q/k carry rope dims, v not)
    g = h // hk
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bq = min(block_q, 128 if skv >= 32768 else block_q)
    while sq % bq:
        bq //= 2
    nblk = sq // bq
    off = skv - sq
    qg = q.reshape(b, hk, g, sq, d)
    cols = jnp.arange(skv)[None, :]

    @jax.checkpoint
    def body(carry, _):
        i, = carry
        qb = jax.lax.dynamic_slice(qg, (0, 0, 0, i * bq, 0),
                                   (b, hk, g, bq, d))
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        rows = (i * bq + off) + jnp.arange(bq)[:, None]
        mask = jnp.ones((bq, skv), bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ob = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        return (i + 1,), ob.astype(out_dtype or q.dtype)

    _, blocks = jax.lax.scan(body, (jnp.zeros((), jnp.int32),), None,
                             length=nblk)
    # (nblk, B, Hk, g, bq, Dv) → (B, H, Sq, Dv)
    o = jnp.moveaxis(blocks, 0, 3).reshape(b, hk, g, sq, vd)
    return o.reshape(b, h, sq, vd)


def mamba_scan_xla_chunked(x, dt, a, b_in, c_in, d_skip, *, h0=None,
                           chunk: int = 64, out_dtype=None):
    """Memory-bounded selective scan for the XLA path: outer scan over
    chunks with a checkpointed body (mirrors the Pallas kernel's structure —
    only the (B, D, N) state crosses chunk boundaries; the per-timestep
    intermediates inside a chunk are recomputed in backward).  Without this,
    backward saves (B, D, N) per *timestep* — petabytes at L=512k."""
    from repro.distributed.sharding import constrain
    bsz, l, dch = x.shape
    n = a.shape[1]
    while l % chunk:
        chunk //= 2
    nchunks = l // chunk
    if h0 is None:
        h0 = jnp.zeros((bsz, dch, n), jnp.float32)
    h0 = constrain(h0, ("batch", "ssm_inner", None))
    af = a.astype(jnp.float32)
    ds = d_skip.astype(jnp.float32)

    def sl(t, i):
        return jax.lax.dynamic_slice_in_dim(t, i * chunk, chunk, axis=1)

    @jax.checkpoint
    def chunk_body(carry, i):
        h = carry
        xc, dtc = sl(x, i).astype(jnp.float32), sl(dt, i).astype(jnp.float32)
        bc, cc = sl(b_in, i).astype(jnp.float32), sl(c_in, i).astype(jnp.float32)

        def step(h, inp):
            xt, dtt, bt, ct = inp
            da = jnp.exp(dtt[..., None] * af[None])
            h = h * da + (dtt * xt)[..., None] * bt[:, None, :]
            y = jnp.einsum("bdn,bn->bd", h, ct)
            return h, y

        inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (xc, dtc, bc, cc))
        h, ys = jax.lax.scan(step, h, inputs)
        h = constrain(h, ("batch", "ssm_inner", None))
        y = jnp.moveaxis(ys, 0, 1) + xc * ds[None, None]
        return h, y.astype(out_dtype or x.dtype)

    h_fin, ys = jax.lax.scan(chunk_body, h0, jnp.arange(nchunks))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, l, dch)
    return y, h_fin


def mamba_scan_ref(x, dt, a, b_in, c_in, d_skip, *, h0=None, out_dtype=None):
    """Selective state-space scan oracle.

    x, dt: (B, L, D);  a: (D, N) (log-space negative);  b_in, c_in: (B, L, N);
    d_skip: (D,).  Returns (y (B, L, D), h_final (B, D, N)).
    """
    bsz, l, dch = x.shape
    n = a.shape[1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    bf, cf = b_in.astype(jnp.float32), c_in.astype(jnp.float32)
    af = a.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,D) (B,D) (B,N) (B,N)
        da = jnp.exp(dtt[..., None] * af[None])          # (B, D, N)
        db = dtt[..., None] * bt[:, None, :]             # (B, D, N)
        h = h * da + db * xt[..., None]
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    h0 = jnp.zeros((bsz, dch, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    inputs = (
        jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0),
    )
    h_fin, ys = jax.lax.scan(step, h0, inputs)
    y = jnp.moveaxis(ys, 0, 1) + xf * d_skip.astype(jnp.float32)[None, None]
    return y.astype(out_dtype or x.dtype), h_fin


# --------------------------------------------------------------------------
# Convolution (paper §III-B)
# --------------------------------------------------------------------------

def conv2d_ref(x, w, *, stride=1, out_dtype=None):
    """NHWC direct convolution oracle (VALID padding).

    x (N, H, W, C); w (R, S, C, K)."""
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out.astype(out_dtype or x.dtype)
