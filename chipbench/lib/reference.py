"""The plain reference: a dense decoder's forward pass in float32, one layer
at a time, in ``jax.numpy`` alone.

It imports nothing of the system under test and takes nothing it made: the
sizes come from the configuration file, the weights are
drawn again from the seed (``weights.leaf``) and upcast from the bfloat16
the program serves to float32, and every matrix product runs at
``highest`` precision.  Only one layer's weights are on the device at a
time, so a 6B model's float32 reference fits one chip.

The equations are the ones the program states for these models (pre-norm
residual blocks; RMSNorm; rotary position embedding on the first
``rope_fraction`` of each head, rotate-half form; grouped-query causal
attention; SiLU-gated MLP; tied or untied output head).

``rounding="fp8"`` is the control: every weight matrix is rounded to
float8 e4m3 with a per-tensor absmax scale before use.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import weights

LAYER = "groups/0/0/"           # the stacked dense layers in the tree
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("wg", "wu", "wd")


def _round(w, rounding):
    if rounding is None:
        return w
    if rounding != "fp8":
        raise ValueError(f"unknown rounding {rounding!r}")
    top = jnp.float32(jnp.finfo(jnp.float8_e4m3fn).max)
    s = top / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    return (w * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta, fraction):
    """x (T, heads, hd): rotate-half RoPE on the first even(hd·fraction)
    dims of each head at positions 0..T-1."""
    t, _, hd = x.shape
    rot = int(hd * fraction) // 2 * 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


@lru_cache(maxsize=None)
def _layer_fn(h: int, hk: int, hd: int, theta: float, fraction: float,
              eps: float):
    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            t = x.shape[0]
            a = _rmsnorm(x, w["norm1"], eps)
            q = _rope((a @ w["wq"]).reshape(t, h, hd), theta, fraction)
            k = _rope((a @ w["wk"]).reshape(t, hk, hd), theta, fraction)
            v = (a @ w["wv"]).reshape(t, hk, hd)
            k, v = jnp.repeat(k, h // hk, 1), jnp.repeat(v, h // hk, 1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
            causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
            o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * hd)
            x = x + o @ w["wo"]
            m = _rmsnorm(x, w["norm2"], eps)
            return x + (jax.nn.silu(m @ w["wg"]) * (m @ w["wu"])) @ w["wd"]

    return layer


def _draw(seed, path, shape, layer=None, rounding=None):
    w = weights.leaf(seed, path, shape, layer).astype(jnp.float32)
    return _round(w, rounding) if w.ndim == 2 else w


SUBPATH = {n: f"attn/{n}" for n in ATTN} | {n: f"mlp/{n}" for n in MLP} \
    | {"norm1": "norm1/scale", "norm2": "norm2/scale"}


def layer_shapes(model: dict) -> dict:
    d, h, hk, hd, ff = (model[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size"))
    return {"wq": (d, h * hd), "wk": (d, hk * hd), "wv": (d, hk * hd),
            "wo": (h * hd, d), "wg": (d, ff), "wu": (d, ff),
            "wd": (ff, d), "norm1": (d,), "norm2": (d,)}


def layer_weights(model: dict, seed: int, i: int, rounding=None) -> dict:
    return {n: _draw(seed, LAYER + SUBPATH[n], shape, i, rounding)
            for n, shape in layer_shapes(model).items()}


def padded_vocab(model: dict) -> int:
    return (model["vocab_size"] + 255) // 256 * 256


def final_hidden(model: dict, seed: int, seqs, length: int, rounding=None):
    """Each token sequence (right-padded to ``length``; causal attention
    keeps padding out of earlier positions) through every layer and the
    final norm → list of (length, d) float32 arrays."""
    d, vp = model["hidden_size"], padded_vocab(model)
    embed = _draw(seed, "embed", (vp, d), rounding=rounding)
    xs = []
    for toks in seqs:
        ids = np.zeros(length, np.int32)
        ids[:len(toks)] = toks
        xs.append(embed[jnp.asarray(ids)])
    del embed
    layer = _layer_fn(model["num_attention_heads"],
                      model["num_key_value_heads"], model["head_dim"],
                      float(model["rope_theta"]),
                      float(model.get("rope_fraction", 1.0)),
                      float(model["rms_norm_eps"]))
    for i in range(model["num_hidden_layers"]):
        w = layer_weights(model, seed, i, rounding)
        xs = [layer(x, w) for x in xs]
        del w
    g = _draw(seed, "final_norm/scale", (d,))
    eps = float(model["rms_norm_eps"])
    return [_rmsnorm(x, g, eps) for x in xs]


def head(model: dict, seed: int, rounding=None):
    """The output head as (d, vocab) float32, padding rows dropped."""
    d, v, vp = model["hidden_size"], model["vocab_size"], padded_vocab(model)
    if model["tie_word_embeddings"]:
        return _draw(seed, "embed", (vp, d), rounding=rounding)[:v].T
    return _draw(seed, "lm_head", (d, vp), rounding=rounding)[:, :v]


@jax.jit
def _stats(hrows, w, targets):
    with jax.default_matmul_precision("highest"):
        logits = hrows @ w
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best - at, jnp.argmax(logits, -1).astype(jnp.int32)


def gaps(hidden, w, positions, targets, chunk: int = 256):
    """For each row ``positions[i]`` of ``hidden``: how far the reference
    logit of ``targets[i]`` lies below the reference's best, and the
    reference's own first token.  → (gap, argmax) numpy arrays."""
    positions = np.asarray(positions, np.int32)
    targets = np.asarray(targets, np.int32)
    out_g, out_a = [], []
    for s in range(0, len(positions), chunk):
        p = np.zeros(chunk, np.int32)
        t = np.zeros(chunk, np.int32)
        n = min(chunk, len(positions) - s)
        p[:n], t[:n] = positions[s:s + n], targets[s:s + n]
        g, a = _stats(hidden[jnp.asarray(p)], w, jnp.asarray(t))
        out_g.append(np.asarray(g)[:n])
        out_a.append(np.asarray(a)[:n])
    if not out_g:
        return np.zeros(0), np.zeros(0, np.int32)
    return np.concatenate(out_g), np.concatenate(out_a)
