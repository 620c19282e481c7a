"""Operations and bytes that an MLA + MoE model's decode needs (DeepSeek-V2),
from the configuration file's shapes and the engine's expert counters,
whatever implements them: a kernel change moves the measured time and
never these counts.  Beside ``counts.py``, which counts dense models.

* Weights are bfloat16 and counted at the true vocabulary slice.
* Latent attention is counted absorbed, as it decodes: per token and
  layer the projections W_qa, W_qb, W_kva, W_kvb's key half folded into the
  query and its value half applied to the context, and W_o; per cached row
  the scores over the latent and rope lanes and the context over the
  latent.  A decode iteration reads each layer's latent-attention weights
  once, each active slot's live latent rows (c − 1 of them) and writes one
  new row.
* The expert layer reads the router and the shared experts once an
  iteration and each held expert that some active token chose
  (``serve.moe_experts_touched``); its operations are the router's and the
  shared experts' per token and 6 · d · ff per assignment on a held expert
  (``serve.moe_assignments``).
"""
from __future__ import annotations

BF16 = 2


def _z(m: dict) -> dict:
    return {"d": m["hidden_size"], "h": m["num_attention_heads"],
            "qr": m["q_lora_rank"], "kvr": m["kv_lora_rank"],
            "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
            "v": m["v_head_dim"], "ff": m["intermediate_size"],
            "ffm": m["moe_intermediate_size"],
            "shared": m["n_shared_experts"],
            "experts": m["n_routed_experts_published"],
            "layers": m["num_hidden_layers"],
            "dense": m["first_k_dense_replace"], "vocab": m["vocab_size"]}


def latent_row_bytes(m: dict) -> int:
    """One token's cached latent in one layer: kv_lora + rope lanes."""
    return BF16 * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def mla_params(m: dict) -> int:
    """One layer's latent-attention weights (norm gains included)."""
    z = _z(m)
    return (z["d"] * z["qr"] + z["qr"] + z["qr"] * z["h"] * (z["nope"]
                                                            + z["rope"])
            + z["d"] * (z["kvr"] + z["rope"]) + z["kvr"]
            + z["kvr"] * z["h"] * (z["nope"] + z["v"]) + z["h"] * z["v"]
            * z["d"])


def mla_token_flops(m: dict) -> int:
    """One decoded token's projections in one layer, absorbed."""
    z = _z(m)
    return 2 * (z["d"] * z["qr"] + z["qr"] * z["h"] * (z["nope"] + z["rope"])
                + z["d"] * (z["kvr"] + z["rope"])
                + z["h"] * z["nope"] * z["kvr"] + z["h"] * z["kvr"] * z["v"]
                + z["h"] * z["v"] * z["d"])


def mla_key_flops(m: dict) -> int:
    """Per cached row and layer: scores over latent + rope, context over
    the latent."""
    z = _z(m)
    return 2 * z["h"] * (2 * z["kvr"] + z["rope"])


def mla_decode(m: dict, iterations: int, contexts) -> tuple[float, float]:
    """(least bytes, FLOPs) of the latent attention of ``iterations``
    decode iterations that decoded tokens at context lengths ``contexts``
    (keys attended, the new one included), over every layer."""
    layers = m["num_hidden_layers"]
    row = latent_row_bytes(m)
    weights = BF16 * layers * mla_params(m)
    nbytes = iterations * weights + sum(layers * row * c for c in contexts)
    flops = sum(layers * (mla_token_flops(m) + mla_key_flops(m) * c)
                for c in contexts)
    return float(nbytes), float(flops)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def moe_fixed_params(m: dict) -> int:
    """One expert layer's router and shared experts."""
    z = _z(m)
    return z["d"] * z["experts"] + 3 * z["d"] * z["ffm"] * z["shared"]


def moe_decode(m: dict, iterations: int, tokens: int, assignments: int,
               touched: int) -> tuple[float, float]:
    """(least bytes, FLOPs) of the expert layers of ``iterations`` decode
    iterations over ``tokens`` decoded tokens, ``assignments`` of them on
    held experts and ``touched`` held experts read, summed over layers."""
    moe_layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    nbytes = BF16 * (iterations * moe_layers * moe_fixed_params(m)
                     + touched * expert_params(m))
    flops = (2 * tokens * moe_layers * moe_fixed_params(m)
             + 2 * assignments * expert_params(m))
    return float(nbytes), float(flops)


def other_params(m: dict) -> int:
    """What a decode iteration reads besides latent attention and the
    expert layers: the dense layers' FFNs, every layer's two norms, the
    final norm and the output head."""
    z = _z(m)
    return (z["dense"] * 3 * z["d"] * z["ff"] + 2 * z["layers"] * z["d"]
            + z["d"] + z["d"] * z["vocab"])


def decode_step(m: dict, iterations: int, contexts, assignments: int,
                touched: int) -> tuple[float, float]:
    """(least bytes, FLOPs) of whole decode iterations: latent attention,
    expert layers, the rest of the weights once an iteration, and per token
    its embedding row and the head."""
    z = _z(m)
    tokens = len(contexts)
    mb, mf = mla_decode(m, iterations, contexts)
    eb, ef = moe_decode(m, iterations, tokens, assignments, touched)
    ob = BF16 * (iterations * other_params(m) + tokens * z["d"])
    of = 2 * tokens * (z["dense"] * 3 * z["d"] * z["ff"]
                       + z["d"] * z["vocab"])
    return float(mb + eb + ob), float(mf + ef + of)
