"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed here even without a chip: it compiles for a
described ``v5e:2x2`` topology, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM) — what interpret mode
cannot show.  Every case runs at minicpm-2b's published widths (d_model
2304, 36 heads of 64, d_ff 5760, vocab 122753 padded to 122880), the paged
decode attention at chatglm3-6b's too (2 KV heads of 128 for 32 query
heads), and checks that the compiled program holds a Pallas kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  Nothing here runs on a device; ``chip_smoke.py`` does.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.fusion import fused_attn_out_apply, fused_gated_mlp_apply
from repro.kernels import ops
from repro.kernels.brgemm import matmul_pallas
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_decode_pallas)

D, H, HD, FF, VOCAB = 2304, 36, 64, 5760, 122880
SLOTS, PAGE, MAX_SEQ = 8, 16, 1024
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def strict_fusion(monkeypatch):
    """A fused graph whose lowering fails must fail the test, not fall
    back to the XLA reference."""
    monkeypatch.setenv("REPRO_FUSION_FALLBACK", "0")


def _compile_has_kernel(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n,activation", [
    (SLOTS, D, D, None),            # decode projection
    (MAX_SEQ, D, FF, "silu"),       # prefill up-projection + activation
    (MAX_SEQ, FF, D, None),         # prefill down-projection
    (SLOTS, D, VOCAB, None),        # decode lm-head width
], ids=["decode_qkvo", "prefill_up_silu", "prefill_down", "decode_lm_head"])
def test_matmul_pallas_compiles(one_chip, m, k, n, activation):
    _compile_has_kernel(
        lambda a, b: matmul_pallas(a, b, activation=activation), one_chip,
        ((m, k), BF16), ((k, n), BF16))


def test_flash_decode_pallas_compiles(one_chip):
    _compile_has_kernel(
        lambda q, k, v, length: flash_decode_pallas(q, k, v, length=length),
        one_chip, ((SLOTS, H, HD), BF16), ((SLOTS, H, MAX_SEQ, HD), BF16),
        ((SLOTS, H, MAX_SEQ, HD), BF16), ((SLOTS,), I32))


@pytest.mark.parametrize("slots,h,hk,hd", [
    (6, H, H, HD),        # minicpm-2b: MHA, Hk·D 2304
    (16, 32, 2, 128),     # chatglm3-6b: GQA g 16, Hk·D 256
], ids=["minicpm-2b", "chatglm3-6b"])
def test_paged_decode_attention_pallas_compiles(one_chip, slots, h, hk, hd):
    """Under the default backend a TPU program takes the paged kernel, and
    nothing in it has the shape of a gathered per-slot padded view."""
    maxp = 2048 // PAGE
    rows = slots * maxp + 1                     # + the trash page
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((slots, h, hd), BF16), ((rows, PAGE, hk * hd), BF16),
        ((rows, PAGE, hk * hd), BF16), ((slots, maxp), I32),
        ((slots,), I32))]
    text = jax.jit(
        lambda q, k, v, table, length: ops.paged_decode_attention(
            q, k, v, table, page_size=PAGE, length=length)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"[{slots},{maxp * PAGE},{hk},{hd}]" not in text


@pytest.mark.parametrize("seq", [256, MAX_SEQ])
def test_derived_prefill_attention_compiles(one_chip, strict_fusion, seq):
    shape = ((1, H, seq, HD), BF16)
    _compile_has_kernel(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        one_chip, shape, shape, shape)


@pytest.mark.parametrize("m", [SLOTS, MAX_SEQ])
def test_fused_gated_mlp_compiles(one_chip, strict_fusion, m):
    _compile_has_kernel(
        lambda x, wg, wu: fused_gated_mlp_apply(x, wg, wu, backend="pallas"),
        one_chip, ((m, D), BF16), ((D, FF), BF16), ((D, FF), BF16))


def test_fused_gated_mlp_backward_compiles(one_chip, strict_fusion):
    def loss(x, wg, wu):
        y = fused_gated_mlp_apply(x, wg, wu, backend="pallas")
        return y.astype(jnp.float32).sum()

    _compile_has_kernel(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                        ((MAX_SEQ, D), BF16), ((D, FF), BF16),
                        ((D, FF), BF16))


@pytest.mark.parametrize("m", [SLOTS, MAX_SEQ])
def test_fused_attn_out_residual_compiles(one_chip, strict_fusion, m):
    _compile_has_kernel(
        lambda o, wo, res: fused_attn_out_apply(o, wo, residual=res,
                                                backend="pallas"),
        one_chip, ((m, H * HD), BF16), ((H * HD, D), BF16), ((m, D), BF16))


def _v2():
    """DeepSeek-V2 at its published widths, one chip's share: 10 of the
    160 experts the router scores."""
    import dataclasses
    from repro.configs import get_config
    return dataclasses.replace(get_config("deepseek_v2_236b"),
                               experts_held=10, first_expert=0)


def _bf16_shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=sharding),
        tree)


@pytest.mark.parametrize("tokens", [32, 4096], ids=["decode", "prefill"])
def test_moe_grouped_product_compiles(one_chip, tokens):
    """One expert layer at DeepSeek-V2's widths (d 5120, 10 held experts
    of width 1536, 2 shared, the router over 160): group-limited routing
    and the dropless grouped product compile, the product as the TPU's
    ragged-dot kernel."""
    from repro.models import blocks as B
    cfg = _v2()
    p = _bf16_shapes(jax.eval_shape(lambda k: B.init_moe(cfg, k),
                                    jax.random.PRNGKey(0)), one_chip)
    x = jax.ShapeDtypeStruct((tokens, cfg.d_model), BF16, sharding=one_chip)
    text = jax.jit(lambda p, x: B.moe_apply(cfg, p, x)).lower(
        p, x).compile().as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text


def test_mla_paged_decode_layer_compiles(one_chip):
    """One latent-attention layer decoding 32 slots over a latent page pool
    of 8,192 positions each, at DeepSeek-V2's widths: the engine's pool of
    the cell's 7 expert layers (576 lanes a token, padded to 640), read at
    a traced layer.  It compiles, and its arguments and temporaries fit one
    v5e."""
    import dataclasses
    from repro.models import blocks as B
    from repro.models import lm
    cfg = _v2()
    slots, maxp = 32, 8192 // PAGE
    p = _bf16_shapes(jax.eval_shape(lambda k: B.init_mla(cfg, k),
                                    jax.random.PRNGKey(0)), one_chip)
    pools = jax.eval_shape(lambda: lm.init_paged_cache(
        dataclasses.replace(cfg, num_layers=8), slots, slots * maxp, PAGE))
    pool = pools["dec"][1][0]["mla"]["latent"]
    assert pool.shape[0] == 7
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((slots, 1, cfg.d_model), BF16), (pool.shape, BF16),
        ((slots,), I32), ((slots, maxp), I32), ((), I32))]

    def layer(p, x, pool, pos, table, at):
        return B.mla_apply(cfg, p, x, positions=pos[:, None],
                           cache={"latent": pool}, cache_pos=pos,
                           page_table=table, page_size=PAGE, layer=at)

    compiled = jax.jit(layer).lower(p, *args).compile()
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < 15.75 * 2 ** 30, used


def _engine_program(cfg, program, sharding, slots=4, max_seq=512):
    """The serving engine's decode segment or batch-1 prefill (a bucket of
    64 tokens) for ``cfg``, compiled with the caches and state donated as
    the engine donates them on a chip, bf16 weights; → (compiled HLO text,
    the pools' rows, page size)."""
    import functools
    from repro.models import lm
    from repro.serve import engine as E

    ecfg = E.EngineConfig(num_slots=slots, page_size=PAGE, max_seq=max_seq)
    maxp = ecfg.max_pages_per_slot
    pages = slots * maxp

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = _bf16_shapes(jax.eval_shape(lambda k: lm.init_params(cfg, k),
                                         jax.random.PRNGKey(0)), sharding)
    caches = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lm.init_paged_cache(cfg, slots, pages, PAGE)))
    state = E.DecodeState(
        tok=sds((slots,), I32), pos=sds((slots,), I32),
        gen=sds((slots,), I32), limit=sds((slots,), I32),
        active=sds((slots,), bool), bad=sds((slots,), bool),
        uids=sds((slots,), jnp.uint32), temp=sds((slots,), jnp.float32),
        top_k=sds((slots,), I32), top_p=sds((slots,), jnp.float32))
    u32, i32, f32 = (sds((), jnp.uint32), sds((), I32),
                     sds((), jnp.float32))
    fn = {"decode_segment": E._decode_segment,
          "prefill": E._prefill_one}[program]
    if program == "decode_segment":
        rest = (sds((slots, maxp), I32), u32, sds((), bool), u32, i32)
    else:
        rest = (sds((1, 64), I32), sds((1, maxp), I32), i32, i32, u32, u32,
                f32, i32, f32, i32, u32, i32)
    jitted = jax.jit(functools.partial(fn, cfg, ecfg),
                     donate_argnums=E.donation_argnums(fn))
    text = jitted.lower(params, caches, state, *rest).compile().as_text()
    return text, pages + 1


def _minicpm_4l():
    import dataclasses
    from repro.configs import get_config
    return dataclasses.replace(get_config("minicpm_2b"), num_layers=4)


def _v2_3l():
    """The dense first layer and 2 expert layers: an unrolled group of one
    and a scanned group of two."""
    import dataclasses
    return dataclasses.replace(_v2(), num_layers=3)


@pytest.mark.parametrize("program", ["decode_segment", "prefill"])
@pytest.mark.parametrize("make_cfg,max_seq", [(_minicpm_4l, 512),
                                              (_v2_3l, 8192)],
                         ids=["minicpm-2b-4l", "deepseek-v2-3l"])
def test_engine_programs_write_pools_in_place(one_chip, make_cfg, max_seq,
                                              program):
    """The paged K/V (MLA: latent) pools pass through the engine's programs
    whole: the layer scan and the decode loop carry them, and each layer
    scatters its new rows in place.  No copy, dynamic-slice or
    dynamic-update-slice has a pool, or one layer of a pool, for its result
    (shape [..., P+1, page, lanes]): any of them moves a whole pool through
    HBM on every step or program call.  4 slots of 512 positions stand for
    the MHA cells' slots.  The latent pool takes the MLA cell's 8,192
    positions a slot: its row count decides the TPU's default layout of a
    pool whose rows do not fill whole 128-lane tiles (rows axis minor, and
    a copy into and out of each program)."""
    import re
    cfg = make_cfg()
    text, rows = _engine_program(cfg, program, one_chip, max_seq=max_seq)
    pool = rf"bf16\[(?:\d+,)*{rows},{PAGE},\d+\]\S*"
    moved = re.findall(
        rf"(%\S+) = {pool} (?:copy|dynamic-slice|dynamic-update-slice)\(",
        text)
    assert not moved, moved
    # the rows are written into the pools in place, as scatters
    assert re.search(rf"= {pool} scatter\(", text)
    if program == "decode_segment" and not cfg.use_mla:
        assert "tpu_custom_call" in text      # the paged kernel reads them
