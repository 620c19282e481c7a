"""Operations and bytes that the model's work needs, from the shapes alone.

They count what the mathematics requires, whatever implements it: a later
kernel change moves the measured time and never these counts.  Sizes come
from a configuration file (``chipbench/configs/<name>.json``).

* Weights are counted at the true vocabulary (padding rows are not work).
* A decode iteration reads every weight once (the output head included;
  the input embedding only for the rows it looks up), reads the live K/V of
  every active slot and writes one new K/V row per slot.
* Attention operations are causal: position p attends to p + 1 keys.
"""
from __future__ import annotations

import json
import pathlib

BF16 = 2


def layer_params(m: dict) -> dict:
    d, h, hk, hd, ff = (m[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size"))
    attn = d * (h + 2 * hk) * hd + h * hd * d
    mlp = 3 * d * ff
    return {"attn": attn, "mlp": mlp, "norms": 2 * d,
            "matmul": attn + mlp}


def param_count(m: dict) -> int:
    """Every parameter of the model: embedding, head if untied, layers,
    final norm."""
    d, v = m["hidden_size"], m["vocab_size"]
    emb = v * d * (1 if m["tie_word_embeddings"] else 2)
    lp = layer_params(m)
    return emb + m["num_hidden_layers"] * (lp["matmul"] + lp["norms"]) + d


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def kv_bytes_per_token(m: dict, dtype_bytes: int = BF16) -> int:
    return (2 * m["num_key_value_heads"] * m["head_dim"] * dtype_bytes
            * m["num_hidden_layers"])


def _attn_macs_per_key(m: dict) -> int:
    """Multiply-adds per query per key over all layers: QKᵀ and PV."""
    return 2 * m["num_attention_heads"] * m["head_dim"] * \
        m["num_hidden_layers"]


def streamed_weight_bytes(m: dict, dtype_bytes: int = BF16) -> int:
    """Weights one decode iteration has to read: every layer, the final
    norm and the output head (the tied embedding counts once, as the
    head)."""
    lp = layer_params(m)
    return dtype_bytes * (m["num_hidden_layers"] * (lp["matmul"] + lp["norms"])
                          + m["hidden_size"] + head_params(m))


def decode_flops(m: dict, contexts) -> float:
    """Decoded tokens at context lengths ``contexts`` (keys attended, the
    new one included)."""
    lp = layer_params(m)
    per_tok = 2 * (m["num_hidden_layers"] * lp["matmul"] + head_params(m))
    return float(sum(per_tok + 2 * _attn_macs_per_key(m) * c
                     for c in contexts))


def decode_bytes(m: dict, iterations: int, contexts) -> float:
    """Least HBM traffic of ``iterations`` decode iterations that decoded
    tokens at context lengths ``contexts``: the weights once an iteration,
    and per token its slot's live K/V read (c − 1 cached rows), its new row
    written and its embedding row looked up."""
    kv = kv_bytes_per_token(m)
    rows = BF16 * m["hidden_size"]
    return float(iterations * streamed_weight_bytes(m)
                 + sum(kv * c + rows for c in contexts))


def prefill_flops(m: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, and the head on its last position."""
    lp = layer_params(m)
    causal_keys = prompt * (prompt + 1) / 2
    return float(2 * prompt * m["num_hidden_layers"] * lp["matmul"]
                 + 2 * _attn_macs_per_key(m) * causal_keys
                 + 2 * head_params(m))


def train_flops(m: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step (3 × forward), no recomputation:
    every matrix product, the head on every position, causal attention."""
    lp = layer_params(m)
    tokens = batch * seq
    fwd = (2 * tokens * (m["num_hidden_layers"] * lp["matmul"]
                         + head_params(m))
           + 2 * _attn_macs_per_key(m) * batch * seq * (seq + 1) / 2)
    return 3.0 * fwd


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((pathlib.Path(__file__).with_name("peaks.json"))
                       .read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
