"""Language-model assembly: layer-pattern groups, training forward/loss,
KV-cache decode — for every assigned architecture family (dense / GQA / MLA /
MoE / SSM / hybrid / enc-dec / VLM backbone).

Layer organization: consecutive layers with identical block structure form
*groups*; each group's parameters are stacked on a leading ``repeat`` axis and
applied with ``lax.scan`` (O(1) HLO size in depth — essential for the 94-layer
dry-runs).  Heterogeneous patterns (gemma3 5:1 local:global, jamba 1:7
attn:mamba with MoE every 2nd layer, deepseek's first dense layer) become a
short ``kinds`` tuple scanned per period.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import tpp
from repro.distributed.sharding import active_rules, constrain
from repro.kernels import ops
from repro.models import blocks as B

__all__ = [
    "LayerGroup", "derive_groups", "init_params", "forward_hidden",
    "lm_loss", "init_cache", "init_paged_cache", "decode_step", "prefill",
    "finite_logits",
]


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    kinds: tuple[tuple[str, bool], ...]   # (block kind, is_moe) per position
    repeat: int


def derive_groups(cfg: ModelConfig) -> list[LayerGroup]:
    sigs = cfg._layer_kinds()
    groups: list[LayerGroup] = []
    k = cfg.first_k_dense
    if k:
        groups.append(LayerGroup(tuple(sigs[:k]), 1))
    rest = sigs[k:]
    if rest:
        period = math.lcm(cfg.pattern_period, cfg.moe_period if cfg.is_moe else 1)
        assert len(rest) % period == 0, (cfg.name, len(rest), period)
        pat = tuple(rest[:period])
        for i, s in enumerate(rest):
            assert s == pat[i % period], (cfg.name, i, s, pat)
        groups.append(LayerGroup(pat, len(rest) // period))
    return groups


# --------------------------------------------------------------------------
# Block init / apply
# --------------------------------------------------------------------------

def _has_ffn(cfg: ModelConfig, kind: str, moe: bool) -> bool:
    return moe or cfg.d_ff > 0


def init_block(cfg: ModelConfig, key, kind: str, moe: bool, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {"norm1": B.init_norm(cfg, ks[0])}
    if kind == "mamba":
        p["mamba"] = B.init_mamba(cfg, ks[1])
    elif cfg.use_mla:
        p["mla"] = B.init_mla(cfg, ks[1])
    else:
        p["attn"] = B.init_attention(cfg, ks[1])
    if cross:
        p["norm_x"] = B.init_norm(cfg, ks[2])
        p["xattn"] = B.init_attention(cfg, ks[3])
    if _has_ffn(cfg, kind, moe):
        p["norm2"] = B.init_norm(cfg, ks[4])
        p["moe" if moe else "mlp"] = (
            B.init_moe(cfg, ks[5]) if moe else B.init_mlp(cfg, ks[5])
        )
    return p


def aux_zero(cfg: ModelConfig) -> dict:
    """What a block adds up besides its output: the MoE load-balance loss,
    and for an MoE model the expert layer's counts (``blocks.moe_apply``).
    The keys depend on the config alone, so a dense model's programs carry
    the loss and nothing more."""
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if cfg.is_moe:
        aux["moe_assignments"] = jnp.zeros((), jnp.int32)
        aux["moe_experts_touched"] = jnp.zeros((), jnp.int32)
    return aux


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def block_apply(cfg: ModelConfig, p, x, *, kind: str, moe: bool,
                cache=None, cache_pos=0, positions=None, xattn_kv=None,
                ep_axis: Optional[str] = None, dropout_seed=None,
                page_table=None, page_size: int = 0, seq_lengths=None,
                token_mask=None, layer=0):
    """Pre-norm residual block.  ``dropout_seed`` (train only, already
    folded per layer) enables the attention-output dropout at
    ``cfg.dropout_rate``.  ``cache_pos`` may be a per-slot ``(B,)`` vector
    and ``page_table``/``page_size`` switch the attention caches to the
    paged pool layout (see ``blocks.attention_apply``): the cache's pools
    are then stacked over the layer group and this block's is ``layer`` of
    the stack; mamba state stays
    per-slot but honours ``seq_lengths`` ((B,) valid-token counts) so
    bucket-padded prefill leaves exact SSM state.  ``token_mask`` ((B,)
    bool) says which sequences the expert layer's counts count.
    Returns (x, new_cache, aux) — ``aux`` as ``aux_zero`` gives it."""
    aux = aux_zero(cfg)
    h = B._norm(cfg, p["norm1"], x)
    new_cache = dict(cache) if cache is not None else None
    res_folded = False
    if kind == "mamba":
        out, c = B.mamba_apply(cfg, p["mamba"], h,
                               cache=cache.get("mamba") if cache else None,
                               length=seq_lengths)
        if new_cache is not None:
            new_cache["mamba"] = c
    elif cfg.use_mla:
        out, c = B.mla_apply(cfg, p["mla"], h, positions=positions,
                             cache=cache.get("mla") if cache else None,
                             cache_pos=cache_pos, page_table=page_table,
                             page_size=page_size, layer=layer)
        if new_cache is not None:
            new_cache["mla"] = c
    else:
        # with use_fusion the block residual is threaded into the fused
        # attention output projection (+residual tail — one kernel for
        # GEMM + add, forward and backward); attention_apply returns the
        # post-residual value, so skip the add below
        res_folded = cfg.use_fusion
        out, c = B.attention_apply(cfg, p["attn"], h, kind=kind,
                                   positions=positions,
                                   cache=cache.get("attn") if cache else None,
                                   cache_pos=cache_pos,
                                   residual=x if res_folded else None,
                                   dropout_seed=dropout_seed,
                                   page_table=page_table,
                                   page_size=page_size, layer=layer)
        if new_cache is not None:
            new_cache["attn"] = c
    x = out if res_folded else x + out
    x = constrain(x, ("batch", "seq", "embed"))

    if "xattn" in p:
        h = B._norm(cfg, p["norm_x"], x)
        out, _ = B.attention_apply(cfg, p["xattn"], h, kind="cross",
                                   xattn_kv=xattn_kv)
        x = x + out

    if _has_ffn(cfg, kind, moe):
        h = B._norm(cfg, p["norm2"], x)
        b, s, d = h.shape
        h2 = h.reshape(b * s, d)
        if moe:
            mask = (None if token_mask is None
                    else jnp.repeat(jnp.asarray(token_mask, bool), s))
            y, aux = _moe_maybe_sharded(cfg, p["moe"], h2, ep_axis, mask)
        else:
            y = B.mlp_apply(cfg, p["mlp"], h2)
        x = x + y.reshape(b, s, d)
        x = constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


def _moe_maybe_sharded(cfg: ModelConfig, p, x2d, ep_axis, token_mask=None):
    """Run the MoE layer under shard_map (EP over ``ep_axis``) when a mesh
    rule set is active; plain single-device execution otherwise."""
    rules = active_rules()
    if ep_axis is None or rules is None or ep_axis not in rules.mesh.shape:
        return B.moe_apply(cfg, p, x2d, ep_axis=None, token_mask=token_mask)
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    # tokens shard over the DP axes only when divisible (long_500k decode has
    # a single token — replicate it instead)
    if dp and x2d.shape[0] % dp_size == 0:
        tok_spec = P(dp, None)
    else:
        tok_spec = P(None, None)
    wspec = {
        "router": P(None, None),
        "wg": P("model", None, None),
        "wu": P("model", None, None),
        "wd": P("model", None, None),
    }
    if "shared" in p:
        wspec["shared"] = jax.tree.map(lambda _: P(), p["shared"])

    if token_mask is None:
        token_mask = jnp.ones((x2d.shape[0],), bool)
    fn = jax.shard_map(
        lambda p, x, m: B.moe_apply(cfg, p, x, ep_axis=ep_axis, token_mask=m),
        mesh=mesh,
        in_specs=(wspec, tok_spec, P(tok_spec[0])),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )
    return fn(p, x2d, token_mask)


# --------------------------------------------------------------------------
# Model init
# --------------------------------------------------------------------------

def _stack_init(cfg, key, kinds, repeat, cross=False):
    """Init `repeat` copies of one period, stacked on the leading axis."""
    def one(k):
        ks = jax.random.split(k, len(kinds))
        return [init_block(cfg, ki, kind, moe, cross=cross)
                for ki, (kind, moe) in zip(ks, kinds)]
    keys = jax.random.split(key, repeat)
    return jax.vmap(one)(keys)


def init_params(cfg: ModelConfig, key):
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.padded_vocab
    params = {
        "embed": jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02,
        "final_norm": B.init_norm(cfg, ks[1]),
    }
    groups = derive_groups(cfg)
    params["groups"] = [
        _stack_init(cfg, k, g.kinds, g.repeat, cross=cfg.is_encdec)
        for k, g in zip(jax.random.split(ks[2], len(groups)), groups)
    ]
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(ks[3], (d, v), jnp.float32) * 0.02
    if cfg.is_encdec:
        enc_kinds = tuple([("bidir", False)] * cfg.encoder_layers)
        params["encoder"] = {
            "groups": [_stack_init(cfg, ks[4], (("bidir", False),),
                                   cfg.encoder_layers)],
            "final_norm": B.init_norm(cfg, ks[5]),
        }
    if cfg.frontend == "vision_stub":
        params["patch_proj"] = jax.random.normal(ks[6], (d, d), jnp.float32) * 0.02
    return params


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

# the cache entries of a block that are page pools when caches are paged
_POOL_KEYS = ("attn", "mla")


def _split_pools(pcache):
    """A period's caches (one dict per block) → (its page pools, the rest:
    mamba state), each a list of dicts of the same length."""
    pools = [{k: c[k] for k in c if k in _POOL_KEYS} for c in pcache]
    rest = [{k: c[k] for k in c if k not in _POOL_KEYS} for c in pcache]
    return pools, rest


def _merge_pools(pools, rest):
    return [{**p, **r} for p, r in zip(pools, rest)]


def _apply_groups(cfg, gparams_list, groups, x, *, caches=None, cache_pos=0,
                  positions=None, xattn_kv=None, ep_axis=None, remat=True,
                  cross=False, unroll=False, dropout_seed=None,
                  page_table=None, page_size=0, seq_lengths=None,
                  token_mask=None):
    """Scan each group over its repeat axis; thread caches and aux loss.

    ``unroll=True`` replaces the depth scan with a trace-time loop — used by
    the dry-run so ``compiled.cost_analysis()`` counts every layer (XLA's
    analysis reports a while-loop body once), at the cost of HLO size.

    Paged caches (``page_table`` given): the attention / latent page pools,
    stacked over the group's repeat axis, are never split by layer.  They
    ride the scan's carry (the unrolled loop's running value) whole, and
    each block writes its new rows into them in place at its index in the
    group (traced in the scan, static unrolled) — slicing a layer out and
    stacking it back would copy every pool each step.  Mamba state, dense
    caches and training keep the ``xs``/``ys`` layout.

    ``dropout_seed`` (traced uint32 scalar) is folded with the absolute
    layer index (``fusion.rng.fold_in``) so every layer draws an independent
    dropout stream from one seed — identical across fused/unfused paths and
    across scan/unroll layouts."""
    total_aux = aux_zero(cfg)
    new_caches = []
    layer_base = 0
    paged = page_table is not None and caches is not None
    for gi, (gparams, group) in enumerate(zip(gparams_list, groups)):
        gcache = caches[gi] if caches is not None else None
        pools = None
        if paged:
            pools, gcache = _split_pools(gcache)

        def period(x, pools, pparams, pcache, lidx0, layer):
            """One period of blocks.  ``pcache`` is this layer's slice of
            the caches but for the pools, which (paged) pass in and out
            whole."""
            if pools is not None:
                pcache = _merge_pools(pools, pcache)
            aux_p = aux_zero(cfg)
            ncache = [] if pcache is not None else None
            for pos_i, (kind, moe) in enumerate(group.kinds):
                if dropout_seed is not None:
                    from repro.fusion import rng as frng
                    seed_i = frng.fold_in(dropout_seed, lidx0 + pos_i)
                else:
                    seed_i = None
                fn = partial(block_apply, cfg, kind=kind, moe=moe,
                             cache_pos=cache_pos, positions=positions,
                             xattn_kv=xattn_kv, ep_axis=ep_axis,
                             dropout_seed=seed_i, page_table=page_table,
                             page_size=page_size, seq_lengths=seq_lengths,
                             token_mask=token_mask, layer=layer)
                if remat:
                    fn = jax.checkpoint(
                        fn, policy=jax.checkpoint_policies.nothing_saveable,
                        static_argnums=(),
                    )
                x, c, aux = fn(
                    pparams[pos_i],
                    x,
                    cache=pcache[pos_i] if pcache is not None else None,
                )
                if ncache is not None:
                    ncache.append(c)
                aux_p = _add(aux_p, aux)
            if pools is not None:
                pools, ncache = _split_pools(ncache)
            return x, pools, ncache, aux_p

        period_len = len(group.kinds)
        if group.repeat == 1 or unroll:
            ncaches_list = []
            for r in range(group.repeat):
                pparams = jax.tree.map(lambda a: a[r], gparams)
                pcache = (jax.tree.map(lambda a: a[r], gcache)
                          if gcache is not None else None)
                x, pools, ncache, aux_p = period(
                    x, pools, pparams, pcache, layer_base + r * period_len, r)
                total_aux = _add(total_aux, aux_p)
                if ncache is not None:
                    ncaches_list.append(ncache)
            ncaches = (jax.tree.map(lambda *xs: jnp.stack(xs), *ncaches_list)
                       if ncaches_list else None)
        else:
            def scan_body(carry, xs):
                x, aux_acc, pools = carry
                pparams, pcache, lidx0, layer = xs
                x, pools, ncache, aux_p = period(
                    x, pools, pparams, pcache, lidx0, layer)
                return (x, _add(aux_acc, aux_p), pools), ncache

            lidx = layer_base + jnp.arange(group.repeat) * period_len
            layers = jnp.arange(group.repeat) if paged else None
            xs = (gparams, gcache, lidx, layers)
            (x, total_aux, pools), ncaches = jax.lax.scan(
                scan_body, (x, total_aux, pools), xs)
        new_caches.append(_merge_pools(pools, ncaches) if paged else ncaches)
        layer_base += group.repeat * period_len
    return x, new_caches if caches is not None else None, total_aux


def _embed(cfg, params, tokens):
    dt = B.compute_dtype(cfg)
    return params["embed"].astype(dt)[tokens]


def _positions_from(pos0, b, s):
    if jnp.ndim(pos0) == 1:          # per-slot (B,) positions (paged decode)
        return jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(s)[None, :]
    return pos0 + jnp.broadcast_to(jnp.arange(s), (b, s))


def forward_hidden(cfg: ModelConfig, params, batch, *, caches=None,
                   cache_pos=0, ep_axis=None, remat=True, unroll=False,
                   dropout_seed=None, page_table=None, page_size=0,
                   seq_lengths=None, token_mask=None):
    """→ (hidden (B, S, d) fp-compute, new_caches, aux) — ``aux`` the sum
    over layers of what ``block_apply`` adds up (``aux_zero``).  ``batch`` keys:
    tokens (B,S) [+ patches (B,P,d) for vlm; frames (B,F,d) for encdec].
    ``dropout_seed`` (train only) enables ``cfg.dropout_rate`` dropout in
    the decoder blocks — per-layer streams are folded in downstream.
    ``cache_pos`` may be a per-slot (B,) vector (continuous batching) and
    ``page_table``/``page_size`` switch attention caches to the paged pool
    layout (see ``init_paged_cache``)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = B.compute_dtype(cfg)
    x = _embed(cfg, params, tokens)
    pos0 = cache_pos
    positions = _positions_from(pos0, b, s)

    xattn_kv = None
    if cfg.is_encdec:
        if caches is not None and caches.get("enc_out") is not None:
            xattn_kv = caches["enc_out"]
        else:
            xattn_kv = encode(cfg, params, batch["frames"], remat=remat,
                              unroll=unroll)

    if cfg.frontend == "vision_stub" and "patches" in batch:
        patches = batch["patches"].astype(dt)
        pp = patches.reshape(-1, cfg.d_model) @ params["patch_proj"].astype(dt)
        x = jnp.concatenate([pp.reshape(patches.shape), x], axis=1)
        positions = _positions_from(pos0, b, x.shape[1])

    x = constrain(x, ("batch", "seq", "embed"))
    groups = derive_groups(cfg)
    dec_caches = caches["dec"] if caches is not None else None
    x, new_dec, aux = _apply_groups(
        cfg, params["groups"], groups, x, caches=dec_caches,
        cache_pos=cache_pos, positions=positions, xattn_kv=xattn_kv,
        ep_axis=ep_axis, remat=remat, unroll=unroll,
        dropout_seed=dropout_seed, page_table=page_table,
        page_size=page_size, seq_lengths=seq_lengths, token_mask=token_mask)
    x = B._norm(cfg, params["final_norm"], x)
    new_caches = None
    if caches is not None:
        new_caches = dict(caches)
        new_caches["dec"] = new_dec
        if xattn_kv is not None:
            new_caches["enc_out"] = xattn_kv
    return x, new_caches, aux


def encode(cfg: ModelConfig, params, frames, *, remat=True, unroll=False):
    """Whisper-style encoder over stub frame embeddings (B, F, d)."""
    dt = B.compute_dtype(cfg)
    enc = params["encoder"]
    x = frames.astype(dt)
    groups = [LayerGroup((("bidir", False),), cfg.encoder_layers)]
    b, f, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(f), (b, f))
    x, _, _ = _apply_groups(cfg, enc["groups"], groups, x,
                            positions=positions, remat=remat, unroll=unroll)
    return B._norm(cfg, enc["final_norm"], x)


# --------------------------------------------------------------------------
# Loss (chunked-vocab cross entropy — never materializes (B,S,V))
# --------------------------------------------------------------------------

def _mask_pad_logits(cfg, logits):
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(pad_mask, logits, -1e30)


def _unembed_weight(cfg, params):
    dt = B.compute_dtype(cfg)
    if cfg.tie_embeddings:
        return params["embed"].astype(dt).T
    return params["lm_head"].astype(dt)


def lm_loss(cfg: ModelConfig, params, batch, *, ep_axis=None, remat=True,
            loss_chunk: int = 512, aux_weight: float = 0.01, unroll=False,
            dropout_seed=None):
    """batch: tokens (B,S), labels (B,S), mask (B,S).  Chunked CE over the
    sequence: logits materialize only (B, chunk, V) at a time.
    ``dropout_seed`` (train step, already folded with the step index)
    enables ``cfg.dropout_rate`` dropout."""
    h, _, aux = forward_hidden(cfg, params, batch, ep_axis=ep_axis,
                               remat=remat, unroll=unroll,
                               dropout_seed=dropout_seed)
    if cfg.frontend == "vision_stub" and "patches" in batch:
        h = h[:, batch["patches"].shape[1]:]
    w = _unembed_weight(cfg, params)
    labels, mask = batch["labels"], batch["mask"].astype(jnp.float32)
    b, s, d = h.shape
    chunk = min(loss_chunk, s)
    assert s % chunk == 0
    nchunks = s // chunk

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_loss(hc, yc, mc):
        # (chunk, B, d) → logits only ever live for one chunk (checkpointed:
        # the backward recomputes them rather than saving nchunks copies);
        # shard on (batch, vocab) — the seq-chunk dim stays local
        logits = jnp.einsum("cbd,dv->cbv", hc, w,
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, (None, "batch", "vocab"))
        if cfg.padded_vocab != cfg.vocab_size:  # mask vocab-padding columns
            pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - ll) * mc), jnp.sum(mc)

    def body(carry, xs):
        tot, cnt = carry
        t, c = chunk_loss(*xs)
        return (tot + t, cnt + c), None

    xs = (
        h.reshape(b, nchunks, chunk, d).transpose(1, 2, 0, 3),
        labels.reshape(b, nchunks, chunk).transpose(1, 2, 0),
        mask.reshape(b, nchunks, chunk).transpose(1, 2, 0),
    )
    with jax.named_scope("lm_head"):
        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                     xs)
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce + aux_weight * aux["loss"]
    return loss, {"ce": ce, "aux": aux["loss"], "tokens": cnt}


# --------------------------------------------------------------------------
# KV-cache decode
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               ring_local: bool = False):
    """Decode caches.  ``ring_local=True`` bounds sliding-window ("local")
    layers to a window-sized ring buffer instead of full length — the §Perf
    long-context optimization (memory ∝ window instead of ∝ seq for 5/6 of
    gemma3's layers); exact because keys carry absolute RoPE before caching
    and softmax is permutation-invariant over the ring."""
    dt = B.compute_dtype(cfg)
    hk, hd = cfg.num_kv_heads, cfg.head_dim

    def block_cache(kind, moe):
        c = {}
        if kind == "mamba":
            c["mamba"] = {
                "conv": jnp.zeros((batch_size, cfg.ssm_conv - 1, cfg.d_inner), dt),
                "h": jnp.zeros((batch_size, cfg.d_inner, cfg.ssm_state),
                               jnp.float32),
            }
        elif cfg.use_mla:
            c["mla"] = {"latent": jnp.zeros(
                (batch_size, max_seq, cfg.kv_lora_rank + cfg.rope_head_dim), dt)}
        else:
            smax = max_seq
            if ring_local and kind == "local" and cfg.sliding_window:
                smax = min(max_seq, cfg.sliding_window)
            c["attn"] = {
                "k": jnp.zeros((batch_size, hk, smax, hd), dt),
                "v": jnp.zeros((batch_size, hk, smax, hd), dt),
            }
        return c

    groups = derive_groups(cfg)
    dec = []
    for g in groups:
        percopy = [block_cache(kind, moe) for kind, moe in g.kinds]
        dec.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeat,) + a.shape).copy(), percopy))
    return {"dec": dec, "enc_out": None}


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int):
    """Paged decode caches: attention K/V live in shared page *pools* indexed
    by a per-slot page table instead of per-slot dense buffers.  Pools carry
    ``num_pages + 1`` rows — the last row is the *trash page*: page-table
    entries of empty/retired slots point at it, so their writes land harmlessly
    outside every live request's pages (reads are length-masked anyway).

    Mamba/conv state is O(1) per slot, so it stays a dense per-slot buffer
    exactly like ``init_cache`` (the engine slices/merges it on slot swap)."""
    if cfg.is_encdec:
        raise NotImplementedError(
            "paged serving does not support encoder-decoder models")
    dt = B.compute_dtype(cfg)
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    rows = num_pages + 1  # + trash page

    def block_cache(kind, moe):
        c = {}
        if kind == "mamba":
            c["mamba"] = {
                "conv": jnp.zeros((num_slots, cfg.ssm_conv - 1, cfg.d_inner), dt),
                "h": jnp.zeros((num_slots, cfg.d_inner, cfg.ssm_state),
                               jnp.float32),
            }
        elif cfg.use_mla:
            # a latent row padded to whole 128-lane tiles (DeepSeek-V2's
            # 576 to 640), as the TPU's (8·k, 128) tiling lays it anyway:
            # unpadded, its default layout puts the rows axis minor, and
            # each program then copies the whole pool into a row-major
            # layout and back
            lanes = -(-(cfg.kv_lora_rank + cfg.rope_head_dim) // 128) * 128
            c["mla"] = {"latent": jnp.zeros((rows, page_size, lanes), dt)}
        else:
            # token-major (rows, page_size, hk·hd), heads folded into the
            # last axis: the TPU tiles an array's last two dims by
            # (8·k, 128), so (page_size, hk·hd) lies unpadded where
            # (hk, hd) = (36, 64) (minicpm-2b) pads each pool 2.2×
            c["attn"] = {
                "k": jnp.zeros((rows, page_size, hk * hd), dt),
                "v": jnp.zeros((rows, page_size, hk * hd), dt),
            }
        return c

    groups = derive_groups(cfg)
    dec = []
    for g in groups:
        percopy = [block_cache(kind, moe) for kind, moe in g.kinds]
        dec.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeat,) + a.shape).copy(), percopy))
    return {"dec": dec, "enc_out": None}


def prefill(cfg: ModelConfig, params, caches, batch, *, ep_axis=None,
            unroll=False, page_table=None, page_size=0, logit_index=None):
    """Process the prompt (writes caches at offset 0); returns
    (last-token logits (B,V), caches).  ``logit_index`` ((B,) int32) reads
    logits at a per-row position instead of ``-1`` — used by the engine when
    prompts are right-padded to a shape bucket (it doubles as the mamba
    valid-length mask, so SSM state is exact despite padding)."""
    seq_lengths = None
    if logit_index is not None:
        seq_lengths = jnp.asarray(logit_index, jnp.int32) + 1
    h, caches, _ = forward_hidden(cfg, params, batch, caches=caches,
                                  cache_pos=0, ep_axis=ep_axis, remat=False,
                                  unroll=unroll, page_table=page_table,
                                  page_size=page_size,
                                  seq_lengths=seq_lengths)
    if logit_index is None:
        h_last = h[:, -1]
    else:
        h_last = h[jnp.arange(h.shape[0]), jnp.asarray(logit_index, jnp.int32)]
    with jax.named_scope("lm_head"):
        w = _unembed_weight(cfg, params)
        logits = h_last.astype(jnp.float32) @ w.astype(jnp.float32)
        return _mask_pad_logits(cfg, logits), caches


def decode_step(cfg: ModelConfig, params, caches, tokens, pos, *,
                ep_axis=None, unroll=False, page_table=None, page_size=0,
                token_mask=None):
    """One decode step: tokens (B,) int32, ``pos`` scalar int32 position —
    or per-slot (B,) positions for continuous batching.
    Returns (logits (B,V), new caches); with ``token_mask`` ((B,) bool, the
    slots whose tokens count), also the expert layer's counts summed over
    layers (``aux_zero``'s ``moe_*`` keys; empty for a dense model)."""
    batch = {"tokens": tokens[:, None]}
    h, caches, aux = forward_hidden(cfg, params, batch, caches=caches,
                                    cache_pos=pos, ep_axis=ep_axis,
                                    remat=False, unroll=unroll,
                                    page_table=page_table,
                                    page_size=page_size,
                                    token_mask=token_mask)
    with jax.named_scope("lm_head"):
        w = _unembed_weight(cfg, params)
        logits = h[:, -1].astype(jnp.float32) @ w.astype(jnp.float32)
        logits = constrain(logits, ("batch", "vocab"))
        logits = _mask_pad_logits(cfg, logits)
    if token_mask is None:
        return logits, caches
    return logits, caches, {k: v for k, v in aux.items() if k != "loss"}


def finite_logits(logits) -> jax.Array:
    """(B, V) → (B,) bool: True where every logit is finite.  The serving
    engine's quarantine guard — a NaN/Inf row fails only its own request,
    never the batch."""
    return jnp.all(jnp.isfinite(logits), axis=-1)
