"""Transformer / SSM building blocks, written TPP-style.

Every contraction routes through ``repro.kernels.ops`` (backend-dispatched:
XLA reference on CPU / dry-run, Pallas kernels on TPU), and every elementwise
/ normalization op is a TPP from ``repro.core.tpp`` — the same layering the
paper uses for its fused BERT/LLM layers (§IV-A): BRGEMM cores + TPP epilogues
on 2D tiles, with the outer loops delegated to the schedule layer.

All blocks are pure functions over parameter pytrees:
  params are stored fp32 (master), cast to the config compute dtype at use;
  normalization statistics and attention softmax run fp32 (precision-aware
  TPP contract).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import tpp
from repro.distributed.sharding import constrain
from repro.kernels import ops

# --------------------------------------------------------------------------
# Parameter helpers
# --------------------------------------------------------------------------

def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


def _cast(p, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, p
    )


def compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return tpp.layernorm(x, p["scale"], p["bias"])
    return tpp.rmsnorm(x, p["scale"])


def init_norm(cfg: ModelConfig, key):
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


# --------------------------------------------------------------------------
# RoPE (full / partial-fraction "2D" variants)
# --------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor, ``0.1·m·ln(s) + 1`` (1 for
    s ≤ 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(yarn, rot: int, theta: float) -> tuple[int, int]:
    """The pair indices between which YaRN ramps from interpolated to
    original frequencies: those that turn ``beta_fast`` and ``beta_slow``
    times over the original context, floored / ceiled and clipped."""
    def dim(turns):
        return (rot * math.log(yarn.original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(yarn.beta_fast)), 0),
            min(math.ceil(dim(yarn.beta_slow)), rot - 1))


def rope_inv_freq(rot: int, theta: float, yarn=None) -> np.ndarray:
    """Inverse frequencies of the ``rot // 2`` rotated pairs (float32);
    with ``yarn`` (``configs.base.Yarn``), DeepSeek-V2's blend of the
    original frequencies and those divided by ``yarn.factor``."""
    half = rot // 2
    extra = theta ** (-np.arange(half, dtype=np.float32) / half)
    if yarn is None:
        return extra
    low, high = yarn_correction_range(yarn, rot, theta)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1 → original frequency
    return (extra / yarn.factor * (1.0 - keep) + extra * keep).astype(
        np.float32)


def apply_rope(x, positions, *, theta: float, fraction: float = 1.0,
               yarn=None):
    """x (B, S, H, D); positions (B, S).  Rotates the first
    ``even(D*fraction)`` dims (chatglm/glm4 half-dim RoPE = fraction 0.5,
    gptj = 0.25), passes the rest through.  ``yarn`` swaps in YaRN's
    frequencies and scales cos / sin by mscale ÷ mscale_all_dim."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(rope_inv_freq(rot, theta, yarn))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    xr = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([xr.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------------------
# Paged-cache indexing helpers (serving engine; see serve/kvcache.py)
# --------------------------------------------------------------------------

def _page_lookup(page_table, idx):
    """page_table (B, maxp) int32 → page ids for per-token page indices
    ``idx`` (B, T).  Out-of-range indices clip to the last column, which the
    allocator fills with the trash-page sentinel — writes for padding /
    retired slots land in the scratch page and reads are length-masked."""
    idx = jnp.clip(idx, 0, page_table.shape[-1] - 1)
    return jnp.take_along_axis(page_table, idx, axis=1)


# --------------------------------------------------------------------------
# GQA attention (causal / sliding-window / bidirectional) with KV cache
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key):
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (d, h * hd)),
        "wk": _init(ks[1], (d, hk * hd)),
        "wv": _init(ks[2], (d, hk * hd)),
        "wo": _init(ks[3], (h * hd, d), scale=1.0 / math.sqrt(h * hd)),
    }


@ops.scoped("attention")
def attention_apply(cfg: ModelConfig, p, x, *, kind: str = "attn",
                    positions=None, cache=None, cache_pos=None,
                    xattn_kv=None, residual=None, dropout_seed=None,
                    page_table=None, page_size: int = 0, layer=0):
    """x (B, S, d).  kind ∈ {attn, local, global, bidir, cross}.

    Training/prefill: cache None.  Decode: S == 1, ``cache`` = dict(k, v)
    ring buffers (B, Hk, S_max, hd), ``cache_pos`` scalar write index — or a
    ``(B,)`` vector of per-slot positions (continuous batching: every slot
    sits at its own sequence length; attention masks by ``pos + 1``).

    Paged mode (``page_table`` (B, maxp) int32 + static ``page_size``): the
    cache arrays are token-major page *pools* (L, P, page_size, Hk·hd)
    stacked over the layers of a layer group and shared by all slots; this
    layer's pool is ``layer`` (int or int32 scalar) of the stack, which is
    never sliced out: the new rows are written into it in place and the
    whole stack is returned.  A slot's logical sequence lives in the pages
    its table row names.  Decode scatters the new K/V into (layer, page,
    offset) and attends over the slot's live pages; prefill (S > 1, from
    position 0) attends over the in-chunk K/V and records them into the
    slot's pages for later decode.
    ``residual`` (B, S, d): when given, the block residual is folded into
    the output projection — with ``cfg.use_fusion`` it rides the
    ``fused_attn_out_graph`` ``+residual`` tail inside the same kernel as
    the GEMM, so the caller must NOT add it again.

    ``dropout_seed`` (traced uint32 scalar, train only): enables the
    post-projection dropout at ``cfg.dropout_rate``.  Both paths draw the
    SAME counter-based bits (``fusion.rng``) over the (B·S, d) projection —
    fused inside the output-projection kernel (``dropout_rng`` epilogue,
    no mask tensor), reference via ``rng.dropout`` — so fused and unfused
    training trajectories match under one seed.  ``None`` (inference /
    decode) disables dropout.  Returns (out, new_cache)."""
    dt = compute_dtype(cfg)
    b, s, d = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pw = _cast(p, dt)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    xq = ops.matmul(x.reshape(b * s, d), pw["wq"]).reshape(b, s, h, hd)
    if kind == "cross":
        assert xattn_kv is not None
        enc, enc_s = xattn_kv, xattn_kv.shape[1]
        xk = ops.matmul(enc.reshape(b * enc_s, d), pw["wk"]).reshape(b, enc_s, hk, hd)
        xv = ops.matmul(enc.reshape(b * enc_s, d), pw["wv"]).reshape(b, enc_s, hk, hd)
    else:
        xk = ops.matmul(x.reshape(b * s, d), pw["wk"]).reshape(b, s, hk, hd)
        xv = ops.matmul(x.reshape(b * s, d), pw["wv"]).reshape(b, s, hk, hd)
        xq = apply_rope(xq, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
        xk = apply_rope(xk, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

    q = xq.transpose(0, 2, 1, 3)  # (B, H, S, hd)
    k = xk.transpose(0, 2, 1, 3)
    v = xv.transpose(0, 2, 1, 3)
    if cache is None:
        # TP constraint on the fat per-layer intermediates: heads over model
        # (shape-aware; decode skips this — the KV cache dictates sharding
        # there, and fighting it triggers SPMD full-rematerialization)
        q = constrain(q, ("batch", "heads", None, None))
        k = constrain(k, ("batch", "kv_heads", None, None))
        v = constrain(v, ("batch", "kv_heads", None, None))

    window = cfg.sliding_window if kind == "local" else None
    causal = kind in ("attn", "local", "global")

    new_cache = cache
    if cache is not None and kind != "cross" and page_table is not None:
        assert page_size > 0, "paged cache needs a static page_size"
        if s == 1:
            pos = jnp.asarray(cache_pos, jnp.int32)
            assert pos.ndim == 1, "paged decode takes per-slot (B,) positions"
            pg = _page_lookup(page_table, (pos // page_size)[:, None])[:, 0]
            off = jnp.mod(pos, page_size)
            k_pool = cache["k"].at[layer, pg, off].set(
                k[:, :, 0].reshape(b, hk * hd))
            v_pool = cache["v"].at[layer, pg, off].set(
                v[:, :, 0].reshape(b, hk * hd))
            new_cache = {"k": k_pool, "v": v_pool}
            o = ops.paged_decode_attention(
                q[:, :, 0], k_pool, v_pool, page_table,
                page_size=page_size, length=pos + 1, layer=layer,
                window=window)
            o = o[:, :, None]
        else:
            # whole-prompt prefill (position 0): attention runs on the
            # in-flight K/V; the pages only record them for later decode.
            # Positions past the slot's allocation clip into the trash page.
            if isinstance(cache_pos, int):
                assert cache_pos == 0, "paged prefill starts at position 0"
            tpos = jnp.arange(s, dtype=jnp.int32)
            pg = _page_lookup(page_table,
                              jnp.broadcast_to(tpos // page_size, (b, s)))
            off = jnp.broadcast_to(jnp.mod(tpos, page_size), (b, s))
            k_pool = cache["k"].at[layer, pg, off].set(
                xk.reshape(b, s, hk * hd))
            v_pool = cache["v"].at[layer, pg, off].set(
                xv.reshape(b, s, hk * hd))
            new_cache = {"k": k_pool, "v": v_pool}
            o = ops.attention(q, k, v, causal=causal, window=window)
    elif cache is not None and kind != "cross":
        smax = cache["k"].shape[2]
        # ring buffer: window-bounded local cache (init_cache ring_local) —
        # write at pos % W; once full, its W entries ARE the window, so no
        # window masking is needed (softmax is permutation-invariant and
        # keys carry absolute RoPE)
        is_ring = (kind == "local" and cfg.sliding_window is not None
                   and smax <= cfg.sliding_window)
        if jnp.ndim(cache_pos) == 1:
            # per-slot positions (continuous batching on a dense cache)
            assert s == 1, "vector cache_pos is decode-only (S == 1)"
            pos = jnp.asarray(cache_pos, jnp.int32)
            write_pos = jnp.mod(pos, smax) if is_ring else pos
            bidx = jnp.arange(b)
            k_cache = cache["k"].at[bidx, :, write_pos].set(k[:, :, 0])
            v_cache = cache["v"].at[bidx, :, write_pos].set(v[:, :, 0])
            new_cache = {"k": k_cache, "v": v_cache}
            if is_ring:
                length = jnp.minimum(pos + 1, smax)
                window = None
            else:
                length = pos + 1
            o = ops.decode_attention(q[:, :, 0], k_cache, v_cache,
                                     length=length, window=window)
            o = o[:, :, None]
        else:
            write_pos = (jnp.mod(cache_pos, smax) if is_ring else cache_pos)
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k, (0, 0, write_pos, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v, (0, 0, write_pos, 0))
            new_cache = {"k": k_cache, "v": v_cache}
            if is_ring:
                length = jnp.minimum(
                    jnp.full((b,), cache_pos + s, jnp.int32), smax)
                window = None
            else:
                length = jnp.full((b,), cache_pos + s, jnp.int32)
            if s == 1:
                o = ops.decode_attention(q[:, :, 0], k_cache, v_cache,
                                         length=length, window=window)
                o = o[:, :, None]
            else:  # chunked prefill into the cache
                o = ops.attention(q, k_cache[:, :, : cache_pos + s],
                                  v_cache[:, :, : cache_pos + s],
                                  causal=causal, window=window)
    elif kind == "cross" and cache is not None:
        # cross-attention caches the encoder KV once
        k, v = cache["k"], cache["v"]
        o = ops.attention(q, k, v, causal=False)
    else:
        if cfg.use_fusion:
            # train/prefill attention through the chained-root TppGraph —
            # flash attention *derived* (online softmax as the IR-level
            # softmax_online reducer), with the six-graph recompute backward
            # of fusion.autodiff under jax.grad
            from repro.fusion import fused_attention_apply
            o = fused_attention_apply(q, k, v, causal=causal, window=window)
        else:
            o = ops.attention(q, k, v, causal=causal, window=window)
        if kind == "cross":
            new_cache = {"k": k, "v": v}

    o = o.transpose(0, 2, 1, 3).reshape(b * s, h * hd)
    drop_rate = cfg.dropout_rate if dropout_seed is not None else 0.0
    if cfg.use_fusion:
        # output projection through the fusion compiler; the block residual
        # (lm.block_apply) rides the graph's +residual tail — GEMM, in-kernel
        # PRNG dropout, and residual add in ONE kernel, fused backward (which
        # regenerates the dropout bits) via compile_with_vjp
        from repro.fusion import fused_attn_out_apply
        res2d = residual.reshape(b * s, d) if residual is not None else None
        out = fused_attn_out_apply(
            o, pw["wo"], residual=res2d, dropout_rate=drop_rate,
            dropout_seed=dropout_seed if drop_rate > 0.0 else None,
        ).reshape(b, s, d)
    else:
        out = ops.matmul(o, pw["wo"])
        if drop_rate > 0.0:
            # same counter-based draw over the same (B·S, d) index space and
            # salt as the fused dropout_rng node — bit-identical decisions
            from repro.fusion import rng as frng
            from repro.fusion.library import ATTN_OUT_DROPOUT_SALT
            out = frng.dropout(out, dropout_seed, ATTN_OUT_DROPOUT_SALT,
                               drop_rate)
        out = out.reshape(b, s, d)
        if residual is not None:
            out = residual + out
    return out, new_cache


# --------------------------------------------------------------------------
# MLA attention (deepseek-v2): low-rank latent KV
# --------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    rd, kvr, qr = cfg.rope_head_dim, cfg.kv_lora_rank, cfg.q_lora_rank
    ks = jax.random.split(key, 6)
    return {
        "wq_a": _init(ks[0], (d, qr)),
        "q_norm": jnp.ones((qr,), jnp.float32),
        "wq_b": _init(ks[1], (qr, h * (hd + rd))),
        "wkv_a": _init(ks[2], (d, kvr + rd)),
        "kv_norm": jnp.ones((kvr,), jnp.float32),
        "wkv_b": _init(ks[3], (kvr, h * (hd + hd))),
        "wo": _init(ks[4], (h * hd, d), scale=1.0 / math.sqrt(h * hd)),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-½, times YaRN's mscale(factor, mscale_all_dim)²
    where the model states one (DeepSeek-V2: × 1.2608² = 1.5896)."""
    scale = 1.0 / math.sqrt(cfg.head_dim + cfg.rope_head_dim)
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


@ops.scoped("mla")
def mla_apply(cfg: ModelConfig, p, x, *, positions=None, cache=None,
              cache_pos=None, page_table=None, page_size: int = 0, layer=0):
    """Multi-head Latent Attention.  The KV cache stores only the compressed
    latent (kv_lora + rope_head_dim) per position — the paper-exact memory
    saving.  Train/prefill re-expands K/V through wkv_b; decode uses the
    **absorbed** formulation (scores and context computed directly against
    the latent — O(S·kv_lora) per head instead of O(S·2·head_dim·H) expansion),
    the production deepseek-v2 serving path.

    ``cache_pos`` may be a ``(B,)`` vector of per-slot positions (continuous
    batching).  Paged mode (``page_table`` + ``page_size``): the cache is a
    latent page pool (L, P, page_size, lanes) stacked over a layer group,
    read and written at ``layer`` in place — see :func:`attention_apply`;
    a row holds the kvr+rd latent lanes, zero-padded to ``lanes``."""
    dt = compute_dtype(cfg)
    b, s, d = x.shape
    h, hd, rd, kvr = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    pw = _cast(p, dt)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    q_lat = ops.matmul(x.reshape(b * s, d), pw["wq_a"])
    q_lat = tpp.rmsnorm(q_lat, pw["q_norm"])
    q = ops.matmul(q_lat, pw["wq_b"]).reshape(b, s, h, hd + rd)
    q = constrain(q, ("batch", None, "heads", None))
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta,
                        yarn=cfg.rope_yarn)

    kv = ops.matmul(x.reshape(b * s, d), pw["wkv_a"]).reshape(b, s, kvr + rd)
    c_kv, k_rope = kv[..., :kvr], kv[..., kvr:]
    c_kv = tpp.rmsnorm(c_kv, pw["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta=cfg.rope_theta,
                        yarn=cfg.rope_yarn)

    latent = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)  # (B,S,kvr+rd)
    scale = mla_softmax_scale(cfg)

    new_cache = None
    paged = page_table is not None
    if cache is not None and paged:
        assert page_size > 0, "paged cache needs a static page_size"
        lanes = cache["latent"].shape[-1]
        lat_rows = jnp.pad(latent, ((0, 0), (0, 0), (0, lanes - kvr - rd)))
        if s == 1:
            pos = jnp.asarray(cache_pos, jnp.int32)
            assert pos.ndim == 1, "paged decode takes per-slot (B,) positions"
            pg = _page_lookup(page_table, (pos // page_size)[:, None])[:, 0]
            pool = cache["latent"].at[layer, pg, jnp.mod(pos, page_size)].set(
                lat_rows[:, 0])
            maxp = page_table.shape[-1]
            lat_cache = pool[layer, page_table].reshape(
                b, maxp * page_size, -1)
        else:
            if isinstance(cache_pos, int):
                assert cache_pos == 0, "paged prefill starts at position 0"
            tpos = jnp.arange(s, dtype=jnp.int32)
            pg = _page_lookup(page_table,
                              jnp.broadcast_to(tpos // page_size, (b, s)))
            pool = cache["latent"].at[
                layer, pg, jnp.broadcast_to(jnp.mod(tpos, page_size), (b, s))
            ].set(lat_rows)
        new_cache = {"latent": pool}
    elif cache is not None:
        if jnp.ndim(cache_pos) == 1:
            assert s == 1, "vector cache_pos is decode-only (S == 1)"
            lat_cache = cache["latent"].at[
                jnp.arange(b), jnp.asarray(cache_pos, jnp.int32)
            ].set(latent[:, 0])
        else:
            lat_cache = jax.lax.dynamic_update_slice(
                cache["latent"], latent, (0, cache_pos, 0))
        new_cache = {"latent": lat_cache}
    if cache is not None and s == 1:
        smax = lat_cache.shape[1]
        c_all, kr_all = lat_cache[..., :kvr], lat_cache[..., kvr:kvr + rd]
        wkv_b = pw["wkv_b"].reshape(kvr, h, 2 * hd)
        wk_b, wv_b = wkv_b[..., :hd], wkv_b[..., hd:]
        # absorb wk_b into the query: (B,h,kvr)
        q_abs = jnp.einsum("bhd,khd->bhk", q_nope[:, 0], wk_b,
                           preferred_element_type=jnp.float32)
        scores = (
            jnp.einsum("bhk,bsk->bhs", q_abs, c_all.astype(jnp.float32))
            + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                         kr_all.astype(jnp.float32))
        ) * scale
        length = jnp.asarray(cache_pos) + 1
        if length.ndim == 1:
            length = length[:, None, None]
        mask = jnp.arange(smax)[None, None, :] < length
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhs,bsk->bhk", probs, c_all.astype(jnp.float32))
        o = jnp.einsum("bhk,khd->bhd", ctx, wv_b.astype(jnp.float32))
        o = o[:, None].astype(dt)  # (B,1,h,hd)
    else:
        # train, or prefill-from-zero into the cache (cache_pos must be 0)
        if cache is not None and isinstance(cache_pos, int):
            assert cache_pos == 0, "MLA chunked prefill unsupported; start at 0"
        skv = s
        c_all, kr_all = latent[..., :kvr], latent[..., kvr:]
        kv_exp = ops.matmul(c_all.reshape(b * skv, kvr), pw["wkv_b"]).reshape(
            b, skv, h, 2 * hd)
        kv_exp = constrain(kv_exp, ("batch", None, "heads", None))
        k_nope, v = kv_exp[..., :hd], kv_exp[..., hd:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :], (b, skv, h, rd))],
            axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = ops.attention(
            qf.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, scale=scale,
        ).transpose(0, 2, 1, 3)

    out = ops.matmul(o.reshape(b * s, h * hd), pw["wo"]).reshape(b, s, d)
    return out, new_cache


# --------------------------------------------------------------------------
# MLP (gated / plain) and MoE with expert parallelism
# --------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.gated_mlp:
        return {"wg": _init(ks[0], (d, ff)), "wu": _init(ks[1], (d, ff)),
                "wd": _init(ks[2], (ff, d), scale=1.0 / math.sqrt(ff))}
    return {"wu": _init(ks[0], (d, ff)),
            "wd": _init(ks[1], (ff, d), scale=1.0 / math.sqrt(ff)),
            "bu": jnp.zeros((ff,), jnp.float32),
            "bd": jnp.zeros((d,), jnp.float32)}


@ops.scoped("mlp")
def mlp_apply(cfg: ModelConfig, p, x2d):
    """x2d (T, d) → (T, d).  BRGEMM + fused activation epilogue (paper
    §III-A MLP).

    With ``cfg.use_fusion`` the up-projection is built through the TPP-chain
    fusion compiler (``repro.fusion``): the non-gated GEMM → bias →
    activation chain is a single-root ``TppGraph``, and the gated path's
    ``act(x@wg) * (x@wu)`` runs as ONE two-root graph — both GEMMs share the
    activation lhs inside one nest instead of re-reading it — lowered to one
    fused Pallas kernel (or the composed-TPP reference on the XLA backend)."""
    dt = compute_dtype(cfg)
    pw = _cast(p, dt)
    act = cfg.mlp_activation
    if cfg.gated_mlp:
        if cfg.use_fusion:
            from repro.fusion import fused_gated_mlp_apply
            h = fused_gated_mlp_apply(x2d, pw["wg"], pw["wu"], activation=act)
            return ops.matmul(h, pw["wd"])
        g = ops.matmul(x2d, pw["wg"], activation=act)
        u = ops.matmul(x2d, pw["wu"])
        return ops.matmul(tpp.mul(g, u), pw["wd"])
    if cfg.use_fusion:
        from repro.fusion import fused_mlp_apply
        h = fused_mlp_apply(x2d, pw["wu"], pw["bu"], activation=act)
        return ops.matmul(h, pw["wd"], bias=pw["bd"])
    h = ops.matmul(x2d, pw["wu"], bias=pw["bu"], activation=act)
    return ops.matmul(h, pw["wd"], bias=pw["bd"])


def init_moe(cfg: ModelConfig, key):
    """The router scores all ``num_experts``; the expert weights are the
    ``held_experts`` this device holds, stacked (E_held, fan-in, fan-out)."""
    d, e, ff = cfg.d_model, cfg.held_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, cfg.num_experts), scale=0.02),
        "wg": _init(ks[1], (e, d, ff), scale=1.0 / math.sqrt(d)),
        "wu": _init(ks[2], (e, d, ff), scale=1.0 / math.sqrt(d)),
        "wd": _init(ks[3], (e, ff, d), scale=1.0 / math.sqrt(ff)),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def moe_route(cfg: ModelConfig, logits):
    """Router logits (T, num_experts) → (softmax scores (T, E), weights
    (T, k), expert ids (T, k)), all in float32.  With ``n_group > 1`` the
    top k are taken among the members of the ``topk_group`` groups whose
    best score is highest (DeepSeek-V2's group-limited greedy); weights are
    renormalised (``norm_topk_prob``) or else × ``routed_scaling_factor``."""
    t, e = logits.shape
    k = cfg.experts_per_tok
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if cfg.n_group > 1:
        per = e // cfg.n_group
        best = probs.reshape(t, cfg.n_group, per).max(-1)
        _, keep = jax.lax.top_k(best, cfg.topk_group)
        kept = jnp.any(keep[..., None] == jnp.arange(cfg.n_group), axis=1)
        scores = jnp.where(jnp.repeat(kept, per, axis=1), probs, 0.0)
    topw, topi = jax.lax.top_k(scores, k)
    if cfg.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    else:
        topw = topw * cfg.routed_scaling_factor
    return probs, topw, topi


@ops.scoped("moe")
def moe_apply(cfg: ModelConfig, p, x2d, *, ep_axis: Optional[str] = None,
              token_mask=None):
    """Token-choice top-k MoE over the held experts, dropless: (T, d) →
    ((T, d), aux).

    The router scores every expert (``moe_route``); this device computes,
    for every token, the weighted SiLU-gated FFN of each selected expert
    that it holds, ``[first, first + held)``, and adds the shared experts
    once.  Selections of experts held elsewhere add nothing here: their
    part of the result is another device's.  The held part is one grouped
    product (``ops.grouped_matmul``) over the T·k (token, choice) rows
    sorted by held expert, rows of other experts last — fixed shapes, no
    capacity, no token dropped.

    Under an EP ``shard_map`` (``ep_axis``) the held experts are the
    shard's slice of the device's share, and the partial outputs psum.

    ``aux``: ``loss`` (Switch-style load balance over all experts) and the
    counts over the tokens ``token_mask`` ((T,) bool; all by default)
    keeps: ``moe_assignments`` (token-expert selections that land on held
    experts) and ``moe_experts_touched`` (held experts with a token)."""
    dt = compute_dtype(cfg)
    t, d = x2d.shape
    k = cfg.experts_per_tok
    pw = _cast(p, dt)
    held = pw["wg"].shape[0]

    with jax.named_scope("moe.route"):
        logits = ops.matmul(x2d, pw["router"], out_dtype=jnp.float32)
        probs, topw, topi = moe_route(cfg, logits)
        first = cfg.first_expert
        if ep_axis is not None:
            first = first + jax.lax.axis_index(ep_axis) * held
        local = (topi - first).reshape(-1)                  # (T·k,)
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)                # held → last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)

    # rows sorted by held expert; rows of other experts trail the groups
    rows = x2d[order // k]
    g = ops.grouped_matmul(rows, pw["wg"], sizes, out_dtype=jnp.float32)
    u = ops.grouped_matmul(rows, pw["wu"], sizes, out_dtype=jnp.float32)
    h = (tpp.UNARY_TPPS[cfg.mlp_activation](g) * u).astype(dt)
    ys = ops.grouped_matmul(h, pw["wd"], sizes, out_dtype=jnp.float32)
    # rows of other experts are dropped by `where`, not by a zero weight:
    # what the product leaves in them is not promised
    ys = jnp.where(mine[order][:, None], ys * topw.reshape(-1)[order][:, None],
                   0.0)
    # back to (token, choice) order, so the per-token sum is a k-reduction
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    y = ys[back].reshape(t, k, d).sum(1)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)
    y = y.astype(dt)

    if cfg.num_shared_experts:
        y = y + mlp_apply(cfg, p["shared"], x2d)

    keep = (jnp.ones((t,), bool) if token_mask is None
            else jnp.asarray(token_mask, bool))
    counted = mine & jnp.repeat(keep, k)
    per_expert = jnp.zeros((held,), jnp.int32).at[
        jnp.where(counted, local, held)].add(1, mode="drop")
    aux = {"loss": _moe_aux_loss(probs, topi, cfg.num_experts),
           "moe_assignments": jnp.sum(counted, dtype=jnp.int32),
           "moe_experts_touched": jnp.sum(per_expert > 0, dtype=jnp.int32)}
    if ep_axis is not None:
        for name in ("moe_assignments", "moe_experts_touched"):
            aux[name] = jax.lax.psum(aux[name], ep_axis)
    return y, aux


def _moe_aux_loss(probs, topi, e):
    """Switch-style load-balance auxiliary loss."""
    t, k = topi.shape
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (t * k)
    return e * jnp.sum(me * ce)


# --------------------------------------------------------------------------
# Mamba-1 block (selective SSM)
# --------------------------------------------------------------------------

def init_mamba(cfg: ModelConfig, key):
    d, di, n, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A (log-space)
    a_init = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))
    return {
        "w_in": _init(ks[0], (d, 2 * di)),
        "conv_w": _init(ks[1], (cfg.ssm_conv, di), scale=0.5),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "w_x": _init(ks[2], (di, dr + 2 * n)),
        "w_dt": _init(ks[3], (dr, di), scale=1.0 / math.sqrt(dr)),
        "dt_bias": jnp.full((di,), -2.0, jnp.float32),  # softplus ≈ 0.12
        "a_log": jnp.log(a_init),
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": _init(ks[4], (di, d), scale=1.0 / math.sqrt(di)),
    }


def _causal_conv(w, b, x, state=None):
    """Depthwise causal conv, window c: x (B,S,di).  ``state`` (B, c-1, di)
    carries the decode context.  Returns (y, new_state)."""
    c = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], c - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(c)) + b
    new_state = xp[:, -(c - 1):] if c > 1 else state
    return y.astype(x.dtype), new_state


def mamba_apply(cfg: ModelConfig, p, x, *, cache=None, length=None):
    """x (B, S, d).  cache = {"conv": (B, c-1, di), "h": (B, di, N)} for
    decode continuation.  ``length`` ((B,) int32, optional) marks tokens at
    positions >= length[i] as padding: their SSM update is forced to the
    identity (dt = 0, x = 0) and the conv state is gathered at the true
    boundary, so bucket-padded prefill leaves the exact state a
    length[i]-token sequence would.  Returns (out, new_cache)."""
    dt_ = compute_dtype(cfg)
    b, s, d = x.shape
    di, n, dr = cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank
    pw = _cast(p, dt_)

    xz = ops.matmul(x.reshape(b * s, d), pw["w_in"]).reshape(b, s, 2 * di)
    xi, z = xz[..., :di], xz[..., di:]
    xi = constrain(xi, ("batch", None, "ssm_inner"))
    z = constrain(z, ("batch", None, "ssm_inner"))
    conv_state = cache["conv"] if cache is not None else None
    pad_mask = None
    if length is not None:
        pad_mask = (jnp.arange(s)[None, :] <
                    jnp.asarray(length, jnp.int32)[:, None])[..., None]
        # true conv window ends at the valid-length boundary, not at S
        c = pw["conv_w"].shape[0]
        if c > 1:
            st = (conv_state if conv_state is not None
                  else jnp.zeros((b, c - 1, di), xi.dtype))
            xp = jnp.concatenate([st, xi], axis=1)       # (B, S+c-1, di)
            idx = (jnp.asarray(length, jnp.int32)[:, None]
                   + jnp.arange(c - 1)[None, :])          # window [len, len+c-2]
            boundary_conv = jnp.take_along_axis(xp, idx[..., None], axis=1)
    xi, new_conv = _causal_conv(pw["conv_w"], pw["conv_b"], xi, conv_state)
    if pad_mask is not None and pw["conv_w"].shape[0] > 1:
        new_conv = boundary_conv
    xi = tpp.silu(xi)

    proj = ops.matmul(xi.reshape(b * s, di), pw["w_x"]).reshape(b, s, dr + 2 * n)
    dt_raw = ops.matmul(proj[..., :dr].reshape(b * s, dr), pw["w_dt"]).reshape(b, s, di)
    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"]).astype(dt_)
    b_in, c_in = proj[..., dr:dr + n], proj[..., dr + n:]
    if pad_mask is not None:
        # dt = 0 makes the h recurrence an identity; x = 0 kills the input
        dt_v = jnp.where(pad_mask, dt_v, 0)
        xi = jnp.where(pad_mask, xi, 0)

    a = -jnp.exp(p["a_log"])  # (di, N) fp32
    dt_v = constrain(dt_v, ("batch", None, "ssm_inner"))
    h0 = cache["h"] if cache is not None else None
    y, h_fin = ops.mamba_scan(xi, dt_v, a, b_in, c_in, p["d_skip"], h0=h0)
    y = constrain(tpp.mul(y, tpp.silu(z)), ("batch", None, "ssm_inner"))
    out = ops.matmul(y.reshape(b * s, di), pw["w_out"]).reshape(b, s, d)
    new_cache = {"conv": new_conv, "h": h_fin} if cache is not None else None
    return out, new_cache
