"""The plain reference for DeepSeek-V2 (multi-head latent attention and a
group-routed expert layer): its forward pass in float32, one layer at a
time, in ``jax.numpy`` alone.

Like ``reference.py`` it imports nothing of the system under test: the
sizes come from the configuration file, the weights are drawn again from
the seed (``moe_weights.leaf``) and upcast from the bfloat16 the program
serves, and every matrix product runs at ``highest`` precision.

The equations are DeepSeek-V2's (arXiv:2405.04434; its published
``modeling_deepseek.py``):

* MLA: q = W_qb · RMSNorm(W_qa · x); [c_kv, k_rope] = W_kva · x with c_kv
  RMSNorm'd; K and V **expanded** per head from c_kv by W_kvb (the program
  decodes with W_kvb absorbed instead), k_rope shared by the heads; YaRN
  frequencies on the rope dims; causal softmax at (nope + rope)^-½ ·
  mscale(factor, mscale_all_dim)²; run in blocks of ``BLOCK_Q`` query
  rows, so that 128 heads over 8,192 keys fit;
* the first ``first_k_dense_replace`` layers a SiLU-gated FFN; the others
  score all ``n_routed_experts_published`` router outputs by softmax, keep
  the ``topk_group`` groups whose best member scores highest, take the top
  ``num_experts_per_tok`` experts among them at weight score ×
  ``routed_scaling_factor``, and add up the weighted FFNs of the experts
  this chip holds (``n_routed_experts`` from ``first_expert_held``) and
  the shared experts once;
* the output head over the vocabulary slice.

Departure, shared with the program: RoPE rotates halves of the rope dims,
where the published model rotates interleaved pairs.

``rounding="fp8"`` is the control, as in ``reference.py``: every weight
matrix (each expert's alone) rounded to float8 e4m3 with an absmax scale.
``rounding="bf16"`` is the same equations at the configuration's own
precision: weights as served, and every activation, the latent cache and
the residual stream rounded to bfloat16 where a bfloat16 program holds
them (matrix products and softmaxes still accumulate in float32).  Set
beside the float32 reference it shows how far bfloat16 alone moves the
served tokens on these weights (``serve_moe.judge``).
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import moe_weights
from chipbench.lib.reference import _rmsnorm, _round

BLOCK_Q = 128


def sizes(m: dict) -> dict:
    """The configuration file's numbers under short names."""
    return {"d": m["hidden_size"], "h": m["num_attention_heads"],
            "qr": m["q_lora_rank"], "kvr": m["kv_lora_rank"],
            "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
            "v": m["v_head_dim"], "ff": m["intermediate_size"],
            "ffm": m["moe_intermediate_size"],
            "held": m["n_routed_experts"],
            "first": m.get("first_expert_held", 0),
            "experts": m["n_routed_experts_published"],
            "shared": m["n_shared_experts"], "k": m["num_experts_per_tok"],
            "groups": m["n_group"], "topk_group": m["topk_group"],
            "scale": float(m["routed_scaling_factor"]),
            "norm_topk": bool(m["norm_topk_prob"]),
            "dense": m["first_k_dense_replace"],
            "layers": m["num_hidden_layers"]}


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(model: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = model.get("rope_scaling")
    if not y:
        return extra

    def corr(turns):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / y["factor"] * (1.0 - keep) + extra * keep


def softmax_scale(model: dict) -> float:
    s = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    y = model.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        s *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def _rope(x, freqs, mscale):
    """x (T, heads, r), rotate-half, at positions 0..T-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None] * mscale, jnp.sin(ang)[:, None] * mscale
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def route(z, logits):
    """Router logits (T, E) → gates (T, E): each token's weights on its k
    experts, zero elsewhere."""
    t, e = logits.shape
    p = jax.nn.softmax(logits, -1)
    s = p
    if z["groups"] > 1:
        per = e // z["groups"]
        best = p.reshape(t, z["groups"], per).max(-1)
        _, keep = jax.lax.top_k(best, z["topk_group"])
        kept = jnp.zeros((t, z["groups"]), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        s = jnp.where(jnp.repeat(kept, per, 1), p, 0.0)
    w, top = jax.lax.top_k(s, z["k"])
    w = (w / w.sum(-1, keepdims=True) if z["norm_topk"] else w * z["scale"])
    return jnp.zeros((t, e), jnp.float32).at[jnp.arange(t)[:, None],
                                             top].set(w)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _keep(x):
    return x


def _ffn(x, g, u, d, r=_keep):
    return (r(jax.nn.silu(x @ g) * (x @ u))) @ d


@lru_cache(maxsize=None)
def _layer_fn(key: tuple, moe: bool, eps: float, bf16: bool = False):
    z = dict(key)
    h, nope, rope, v, kvr = (z[n] for n in ("h", "nope", "rope", "v", "kvr"))
    r = _bf16 if bf16 else _keep

    @jax.jit
    def layer(x, w, freqs, mscale, scale):
        with jax.default_matmul_precision("highest"):
            t = x.shape[0]
            a = r(_rmsnorm(x, w["norm1"], eps))
            q = r(r(_rmsnorm(r(a @ w["wq_a"]), w["q_norm"], eps))
                  @ w["wq_b"]).reshape(t, h, nope + rope)
            q = jnp.concatenate([q[..., :nope],
                                 r(_rope(q[..., nope:], freqs, mscale))], -1)
            kv = r(a @ w["wkv_a"])
            c = r(_rmsnorm(kv[:, :kvr], w["kv_norm"], eps))
            kr = r(_rope(kv[:, None, kvr:], freqs, mscale))
            e = r(c @ w["wkv_b"]).reshape(t, h, nope + v)
            k = jnp.concatenate(
                [e[..., :nope], jnp.broadcast_to(kr, (t, h, rope))], -1)
            vals = e[..., nope:]
            keys = jnp.arange(t)

            def block(i):
                qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK_Q, BLOCK_Q)
                s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
                rows = i * BLOCK_Q + jnp.arange(BLOCK_Q)
                s = jnp.where(rows[:, None] >= keys[None, :], s, -jnp.inf)
                p = r(jax.nn.softmax(s, -1))
                return jnp.einsum("hqk,khd->qhd", p, vals)

            o = r(jax.lax.map(block, jnp.arange(t // BLOCK_Q)))
            x = r(x + r(o.reshape(t, h * v) @ w["wo"]))
            m = r(_rmsnorm(x, w["norm2"], eps))
            if not moe:
                return r(x + r(_ffn(m, w["wg"], w["wu"], w["wd"], r))), None
            gate = route(z, m @ w["router"])
            # the same routing on the input rounded to bfloat16, as the
            # program's activations are: where the held picks differ, a
            # near-tie decides the program's route
            gate_bf = route(z, m.astype(jnp.bfloat16).astype(jnp.float32)
                            @ w["router"])
            lo, hi = z["first"], z["first"] + z["held"]
            flips = jnp.any((gate[:, lo:hi] > 0) != (gate_bf[:, lo:hi] > 0),
                            -1)
            y = 0.0
            for j in range(z["held"]):
                y = y + gate[:, lo + j, None] * _ffn(
                    m, w["wg"][j], w["wu"][j], w["wd"][j], r)
            y = r(y) + r(_ffn(m, w["swg"], w["swu"], w["swd"], r))
            return r(x + y), flips

    return layer


def layer_shapes(model: dict, moe: bool) -> dict:
    """{name: (path under the layer, shape)} of one layer's leaves."""
    z = sizes(model)
    d, h = z["d"], z["h"]
    out = {"norm1": ("norm1/scale", (d,)), "norm2": ("norm2/scale", (d,)),
           "wq_a": ("mla/wq_a", (d, z["qr"])),
           "q_norm": ("mla/q_norm", (z["qr"],)),
           "wq_b": ("mla/wq_b", (z["qr"], h * (z["nope"] + z["rope"]))),
           "wkv_a": ("mla/wkv_a", (d, z["kvr"] + z["rope"])),
           "kv_norm": ("mla/kv_norm", (z["kvr"],)),
           "wkv_b": ("mla/wkv_b", (z["kvr"], h * (z["nope"] + z["v"]))),
           "wo": ("mla/wo", (h * z["v"], d))}
    if not moe:
        ff = z["ff"]
        return out | {"wg": ("mlp/wg", (d, ff)), "wu": ("mlp/wu", (d, ff)),
                      "wd": ("mlp/wd", (ff, d))}
    e, ffm, sff = z["held"], z["ffm"], z["ffm"] * z["shared"]
    return out | {"router": ("moe/router", (d, z["experts"])),
                  "wg": ("moe/wg", (e, d, ffm)), "wu": ("moe/wu", (e, d, ffm)),
                  "wd": ("moe/wd", (e, ffm, d)),
                  "swg": ("moe/shared/wg", (d, sff)),
                  "swu": ("moe/shared/wu", (d, sff)),
                  "swd": ("moe/shared/wd", (sff, d))}


def _draw(seed, path, shape, layer=None, rounding=None):
    w = moe_weights.leaf(seed, path, shape, layer).astype(jnp.float32)
    if w.ndim == 3:
        return jnp.stack([_round(m, rounding) for m in w])
    return _round(w, rounding) if w.ndim == 2 else w


def layer_weights(model: dict, seed: int, i: int, rounding=None):
    """Layer ``i``'s weights (float32) and whether it is an expert layer:
    the dense layers lie in the program's group 0, the others in group 1,
    each stacked from 0.  ``rounding`` "fp8" rounds the matrices; "bf16"
    leaves them as served."""
    rounding = rounding if rounding == "fp8" else None
    dense = model["first_k_dense_replace"]
    moe = i >= dense
    group, idx = (1, i - dense) if moe else (0, i)
    return {n: _draw(seed, f"groups/{group}/0/{path}", shape, idx, rounding)
            for n, (path, shape) in layer_shapes(model, moe).items()}, moe


def final_hidden(model: dict, seed: int, seqs, length: int, rounding=None):
    """Each token sequence (right-padded to ``length``, a multiple of
    ``BLOCK_Q``) through every layer and the final norm → (list of
    (length, d) float32 arrays, list per sequence of (expert layers,
    length) bool arrays: where the held experts' picks turn on a
    near-tie)."""
    assert length % BLOCK_Q == 0, length
    z = sizes(model)
    eps = float(model["rms_norm_eps"])
    bf16 = rounding == "bf16"
    embed = _draw(seed, "embed", ((model["vocab_size"] + 255) // 256 * 256,
                                  z["d"]), rounding=None if bf16 else rounding)
    xs = []
    for toks in seqs:
        ids = np.zeros(length, np.int32)
        ids[:len(toks)] = toks
        xs.append(embed[jnp.asarray(ids)])
    del embed
    freqs = jnp.asarray(inv_freq(model), jnp.float32)
    y = model.get("rope_scaling")
    mscale = (yarn_mscale(y["factor"], y["mscale"])
              / yarn_mscale(y["factor"], y["mscale_all_dim"])) if y else 1.0
    key = tuple(sorted(z.items()))
    flips = [[] for _ in seqs]
    for i in range(z["layers"]):
        w, moe = layer_weights(model, seed, i, rounding)
        layer = _layer_fn(key, moe, eps, bf16)
        for j, x in enumerate(xs):
            xs[j], f = layer(x, w, freqs, jnp.float32(mscale),
                             jnp.float32(softmax_scale(model)))
            if f is not None:
                flips[j].append(np.asarray(f))
        del w
    g = _draw(seed, "final_norm/scale", (z["d"],))
    r = _bf16 if bf16 else _keep
    return ([r(_rmsnorm(x, g, eps)) for x in xs],
            [np.stack(f) if f else np.zeros((0, length), bool)
             for f in flips])
