"""Counter-based token sampling (temperature / top-k / top-p).

Every draw is a pure function of ``(seed, request uid, sequence position)``
via the same 20-round threefry2x32 cipher the fused dropout kernels use
(``fusion.rng``): the i-th generated token of a request is identical no
matter which slot the scheduler placed it in, how requests were batched
around it, or how the decode loop was segmented — *seed-deterministic and
schedule-invariant* sampling.

All knobs are per-row vectors, so one jitted sampler serves a
heterogeneous batch (some rows greedy, some at temperature, different
top-k/top-p) without recompilation:

- ``temperature <= 0``  → greedy argmax for that row.
- ``top_k == 0``        → no top-k truncation.
- ``top_p >= 1``        → no nucleus truncation.

Sampling is gumbel-argmax over the filtered, temperature-scaled logits —
no cumulative-probability inversion, one sort for both truncations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.fusion import rng
from repro.kernels.ops import scoped

__all__ = ["SAMPLER_SALT", "sample_tokens"]

SAMPLER_SALT = rng.derive_salt("serve/sampler")


def _filter_logits(logits, top_k, top_p):
    """Mask logits outside the per-row top-k / nucleus sets to -inf.

    One descending sort serves both truncations; the kept set is scattered
    back to vocab order.  The best token is always kept, so the filter can
    never empty a row."""
    v = logits.shape[-1]
    order = jnp.argsort(-logits, axis=-1)                    # (B, V) desc
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    ranks = jnp.arange(v)[None, :]

    k = jnp.where(top_k <= 0, v, top_k)[:, None]             # 0 → off
    keep_k = ranks < k

    probs = jax.nn.softmax(sorted_logits, axis=-1)
    # exclusive cumsum: keep tokens until the mass *before* them reaches p —
    # the standard nucleus rule (first token always kept)
    cum = jnp.cumsum(probs, axis=-1) - probs
    keep_p = cum < jnp.clip(top_p, 0.0, 1.0)[:, None]

    keep_sorted = (keep_k & keep_p) | (ranks == 0)
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(logits.shape[0])[:, None], order].set(keep_sorted)
    return jnp.where(keep, logits, -jnp.inf)


@scoped("sample")
def sample_tokens(logits, *, uids, positions, seed, temperature, top_k,
                  top_p):
    """→ (B,) int32 next tokens.

    logits (B, V) fp32; uids (B,) uint32 request ids; positions (B,) int32
    sequence index of the token being drawn; seed () uint32;
    temperature/top_p (B,) fp32, top_k (B,) int32.  Rows with
    ``temperature <= 0`` take the argmax (no randomness consumed)."""
    logits = logits.astype(jnp.float32)
    b, v = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # per-(request, position) key, then a per-vocab-element counter draw
    k0, k1 = rng.threefry2x32(seed, SAMPLER_SALT, uids, positions)
    bits, _ = rng.threefry2x32(k0[:, None], k1[:, None],
                               jnp.arange(v, dtype=jnp.uint32)[None, :], 0)
    # uniform in (0, 1): 24 mantissa-safe bits, +0.5 keeps it off 0
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * (1.0 / (1 << 24)) \
        + (0.5 / (1 << 24))
    gumbel = -jnp.log(-jnp.log(u))

    filtered = _filter_logits(logits, top_k, top_p)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled_tok = jnp.argmax(filtered / temp + gumbel, axis=-1).astype(
        jnp.int32)
    return jnp.where(temperature <= 0, greedy_tok, sampled_tok)
