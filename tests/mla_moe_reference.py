"""A plain float32 forward pass of DeepSeek-V2 (MLA + group-routed MoE), in
``jax.numpy`` alone, for the CPU tests: no kernels, cache, paging or
batching, and nothing of ``repro.models``.

It reads the program's parameter tree (``lm.init_params``: group 0 the
dense first layers, group 1 the MoE layers, each stacked on a leading
axis) and the configuration's numbers, and follows the published
description:

* MLA: q = W_qb · RMSNorm(W_qa · x); [c_kv, k_rope] = W_kva · x, c_kv
  RMSNorm'd; K and V expanded per head from c_kv by W_kvb (not absorbed);
  the 64 rope dims of q and the one shared k_rope rotated by YaRN's
  frequencies; causal softmax at (nope + rope)^-½ · mscale(factor,
  mscale_all_dim)².
* MoE: softmax over every router output; groups scored by their best
  member, the top ``topk_group`` groups kept, the top k experts among
  their members; weights × ``routed_scaling_factor`` (not renormalised);
  the held experts' SiLU-gated FFNs weighted and summed, then the shared
  experts once.

The one departure it shares with the program: RoPE rotates halves of the
rope dims where DeepSeek-V2 rotates interleaved pairs (a fixed permutation
of random weights' columns).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(dim, base, yarn):
    """DeepSeekV2YarnRotaryEmbedding's ``inv_freq`` for ``dim`` rope dims."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra
    inter = extra / yarn.factor

    def corr(turns):
        return (dim * math.log(yarn.original_max_pos
                               / (turns * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def rope(x, pos, base, yarn):
    """x (T, heads, r) rotate-half at positions ``pos``."""
    f = jnp.asarray(inv_freq(x.shape[-1], base, yarn), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * f
    m = 1.0
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(cfg, w, x):
    t = x.shape[0]
    h, hd, rd, kvr = (cfg.num_heads, cfg.head_dim, cfg.rope_head_dim,
                      cfg.kv_lora_rank)
    pos = jnp.arange(t)
    q = (rmsnorm(x @ w["wq_a"], w["q_norm"]) @ w["wq_b"]).reshape(
        t, h, hd + rd)
    q = jnp.concatenate([q[..., :hd], rope(q[..., hd:], pos, cfg.rope_theta,
                                           cfg.rope_yarn)], -1)
    kv = x @ w["wkv_a"]
    c = rmsnorm(kv[:, :kvr], w["kv_norm"])
    kr = rope(kv[:, None, kvr:], pos, cfg.rope_theta, cfg.rope_yarn)
    e = (c @ w["wkv_b"]).reshape(t, h, 2 * hd)
    k = jnp.concatenate([e[..., :hd], jnp.broadcast_to(kr, (t, h, rd))], -1)
    v = e[..., hd:]
    scale = (hd + rd) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * hd)
    return o @ w["wo"]


def ffn(w, x, names=("wg", "wu", "wd")):
    g, u, d = (w[n] for n in names)
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def route(cfg, logits):
    """→ (weights (T, E), zero outside the k selected experts)."""
    t, e = logits.shape
    p = jax.nn.softmax(logits, -1)
    s = p
    if cfg.n_group > 1:
        per = e // cfg.n_group
        best = p.reshape(t, cfg.n_group, per).max(-1)
        rank = jnp.argsort(-best, -1)[:, :cfg.topk_group]
        kept = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], rank].set(True)
        s = jnp.where(jnp.repeat(kept, per, 1), p, 0.0)
    top = jnp.argsort(-s, -1)[:, :cfg.experts_per_tok]
    wts = jnp.take_along_axis(s, top, -1)
    if cfg.norm_topk_prob:
        wts = wts / wts.sum(-1, keepdims=True)
    else:
        wts = wts * cfg.routed_scaling_factor
    return jnp.zeros((t, e)).at[jnp.arange(t)[:, None], top].set(wts)


def moe(cfg, w, x, gates=None):
    """The config's share of held experts' part plus the shared experts;
    the routing gates (T, E) are appended to ``gates`` where given."""
    first, held = cfg.first_expert, cfg.held_experts
    gate = route(cfg, x @ w["router"])
    if gates is not None:
        gates.append(gate)
    y = jnp.zeros_like(x)
    for j in range(held):
        y = y + gate[:, first + j, None] * ffn(
            {n: w[n][j] for n in ("wg", "wu", "wd")}, x)
    if cfg.num_shared_experts:
        y = y + ffn(w["shared"], x)
    return y


def logits(cfg, params, tokens, gates=None):
    """tokens (T,) → float32 logits (T, vocab) over the true vocabulary;
    each MoE layer's routing gates (T, E) are appended to ``gates``."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for group in f32["groups"]:
            blocks = group[0]
            for r in range(jax.tree.leaves(blocks)[0].shape[0]):
                b = jax.tree.map(lambda a: a[r], blocks)
                x = x + mla(cfg, b["mla"], rmsnorm(x, b["norm1"]["scale"]))
                hn = rmsnorm(x, b["norm2"]["scale"])
                x = x + (moe(cfg, b["moe"], hn, gates) if "moe" in b
                         else ffn(b["mlp"], hn))
        x = rmsnorm(x, f32["final_norm"]["scale"])
        head = f32["lm_head"] if "lm_head" in f32 else f32["embed"].T
        return (x @ head)[:, :cfg.vocab_size]
