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

def apply_rope(x, positions, *, theta: float, fraction: float = 1.0):
    """x (B, S, H, D); positions (B, S).  Rotates the first
    ``even(D*fraction)`` dims (chatglm/glm4 half-dim RoPE = fraction 0.5,
    gptj = 0.25), passes the rest through."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
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


def attention_apply(cfg: ModelConfig, p, x, *, kind: str = "attn",
                    positions=None, cache=None, cache_pos=None,
                    xattn_kv=None, residual=None, dropout_seed=None,
                    page_table=None, page_size: int = 0):
    """x (B, S, d).  kind ∈ {attn, local, global, bidir, cross}.

    Training/prefill: cache None.  Decode: S == 1, ``cache`` = dict(k, v)
    ring buffers (B, Hk, S_max, hd), ``cache_pos`` scalar write index — or a
    ``(B,)`` vector of per-slot positions (continuous batching: every slot
    sits at its own sequence length; attention masks by ``pos + 1``).

    Paged mode (``page_table`` (B, maxp) int32 + static ``page_size``): the
    cache arrays are token-major page *pools* (P, page_size, Hk·hd) shared
    by all slots;
    a slot's logical sequence lives in the pages its table row names.  Decode
    scatters the new K/V into (page, offset) and attends over the gathered
    per-slot view; prefill (S > 1, from position 0) attends over the in-chunk
    K/V and records them into the slot's pages for later decode.
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
            k_pool = cache["k"].at[pg, off].set(k[:, :, 0].reshape(b, hk * hd))
            v_pool = cache["v"].at[pg, off].set(v[:, :, 0].reshape(b, hk * hd))
            new_cache = {"k": k_pool, "v": v_pool}
            o = ops.paged_decode_attention(
                q[:, :, 0], k_pool, v_pool, page_table,
                page_size=page_size, length=pos + 1, window=window)
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
            k_pool = cache["k"].at[pg, off].set(xk.reshape(b, s, hk * hd))
            v_pool = cache["v"].at[pg, off].set(xv.reshape(b, s, hk * hd))
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


def mla_apply(cfg: ModelConfig, p, x, *, positions=None, cache=None,
              cache_pos=None, page_table=None, page_size: int = 0):
    """Multi-head Latent Attention.  The KV cache stores only the compressed
    latent (kv_lora + rope_head_dim) per position — the paper-exact memory
    saving.  Train/prefill re-expands K/V through wkv_b; decode uses the
    **absorbed** formulation (scores and context computed directly against
    the latent — O(S·kv_lora) per head instead of O(S·2·head_dim·H) expansion),
    the production deepseek-v2 serving path.

    ``cache_pos`` may be a ``(B,)`` vector of per-slot positions (continuous
    batching).  Paged mode (``page_table`` + ``page_size``): the cache is a
    latent page pool (P, page_size, kvr+rd) shared by all slots — see
    :func:`attention_apply`."""
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
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)

    kv = ops.matmul(x.reshape(b * s, d), pw["wkv_a"]).reshape(b, s, kvr + rd)
    c_kv, k_rope = kv[..., :kvr], kv[..., kvr:]
    c_kv = tpp.rmsnorm(c_kv, pw["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta=cfg.rope_theta)

    latent = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)  # (B,S,kvr+rd)
    scale = 1.0 / math.sqrt(hd + rd)

    new_cache = None
    paged = page_table is not None
    if cache is not None and paged:
        assert page_size > 0, "paged cache needs a static page_size"
        if s == 1:
            pos = jnp.asarray(cache_pos, jnp.int32)
            assert pos.ndim == 1, "paged decode takes per-slot (B,) positions"
            pg = _page_lookup(page_table, (pos // page_size)[:, None])[:, 0]
            pool = cache["latent"].at[pg, jnp.mod(pos, page_size)].set(
                latent[:, 0])
        else:
            if isinstance(cache_pos, int):
                assert cache_pos == 0, "paged prefill starts at position 0"
            tpos = jnp.arange(s, dtype=jnp.int32)
            pg = _page_lookup(page_table,
                              jnp.broadcast_to(tpos // page_size, (b, s)))
            pool = cache["latent"].at[
                pg, jnp.broadcast_to(jnp.mod(tpos, page_size), (b, s))
            ].set(latent)
        new_cache = {"latent": pool}
        maxp = page_table.shape[-1]
        lat_cache = pool[page_table].reshape(b, maxp * page_size, -1)
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
        c_all, kr_all = lat_cache[..., :kvr], lat_cache[..., kvr:]
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
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, e), scale=0.02),
        "wg": _init(ks[1], (e, d, ff)),
        "wu": _init(ks[2], (e, d, ff)),
        "wd": _init(ks[3], (e, ff, d), scale=1.0 / math.sqrt(ff)),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def _expert_ffn(cfg, wg, wu, wd, xe):
    """xe (E_loc, C, d) → (E_loc, C, d): batched gated FFN over local experts.

    With ``cfg.use_fusion`` each expert's gated up-projection runs through the
    two-root ``fused_gated_mlp_graph`` (per-expert 2D GEMMs; E_loc is a small
    static count, so the unrolled loop stays cheap and every expert reuses
    the same memoized compiled graph)."""
    if cfg.use_fusion:
        from repro.fusion import fused_gated_mlp_apply
        h = jnp.stack([
            fused_gated_mlp_apply(xe[e], wg[e], wu[e],
                                  activation=cfg.mlp_activation)
            for e in range(xe.shape[0])
        ]).astype(xe.dtype)
        return jnp.einsum("ecf,efd->ecd", h, wd,
                          preferred_element_type=jnp.float32).astype(xe.dtype)
    act = tpp.UNARY_TPPS[cfg.mlp_activation]
    g = jnp.einsum("ecd,edf->ecf", xe, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xe, wu, preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wd, preferred_element_type=jnp.float32
                      ).astype(xe.dtype)


def moe_apply(cfg: ModelConfig, p, x2d, *, ep_axis: Optional[str] = None):
    """Token-choice top-k MoE with capacity-bounded dispatch (T, d) → (T, d).

    Expert parallelism: when ``ep_axis`` is set (inside shard_map), tokens are
    replicated over the axis, expert weights sharded over it; each shard
    gathers its local experts' tokens, runs the batched FFN, scatters back and
    psums the partial outputs — EP-as-TP, deterministic fixed-shape
    collectives for the dry-run (DESIGN.md §5).
    """
    dt = compute_dtype(cfg)
    t, d = x2d.shape
    e, k = cfg.num_experts, cfg.experts_per_tok
    pw = _cast(p, dt)

    logits = ops.matmul(x2d, pw["router"], out_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                    # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    if ep_axis is not None:
        w = jax.lax.psum(1, ep_axis)
        shard = jax.lax.axis_index(ep_axis)
        e_loc = e // w
    else:
        w, shard, e_loc = 1, 0, e

    # per-expert capacity; a token contributes at most once per expert, so
    # t is the dropless upper bound (reduced test configs set a huge
    # capacity_factor to make routing exactly dropless)
    cap = int(min(t, max(1, math.ceil(cfg.capacity_factor * t * k / e))))

    # slot ranking within each expert (capacity-drop beyond `cap`)
    flat_e = topi.reshape(-1)                               # (T*k,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    first_occ = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(t * k) - first_occ
    rank = jnp.zeros((t * k,), jnp.int32).at[sort_idx].set(rank_sorted.astype(jnp.int32))

    local_e = flat_e - shard * e_loc
    in_shard = (local_e >= 0) & (local_e < e_loc) & (rank < cap)
    slot = jnp.where(in_shard, local_e * cap + rank, e_loc * cap)  # OOB → drop

    xe = jnp.zeros((e_loc * cap + 1, d), dt)
    token_of = jnp.repeat(jnp.arange(t), k)
    xe = xe.at[slot].set(x2d[token_of], mode="drop")
    xe = xe[: e_loc * cap].reshape(e_loc, cap, d)

    ye = _expert_ffn(cfg, pw["wg"], pw["wu"], pw["wd"], xe)

    ye_flat = jnp.concatenate([ye.reshape(e_loc * cap, d),
                               jnp.zeros((1, d), dt)], axis=0)
    contrib = ye_flat[slot] * topw.reshape(-1)[:, None].astype(dt)
    contrib = jnp.where(in_shard[:, None], contrib, 0)
    # combine without a scatter: slot order is (token, k)-major, so the
    # per-token sum is a reshape + k-reduction (fp32 accumulate) — avoids
    # XLA materializing (T·k, d) fp32 buffers + u32 index arrays
    y = jnp.einsum("tkd->td", contrib.reshape(t, k, d),
                   preferred_element_type=jnp.float32)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)
    y = y.astype(dt)

    if cfg.num_shared_experts:
        y = y + mlp_apply(cfg, p["shared"], x2d)

    aux = _moe_aux_loss(probs, topi, e)
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
