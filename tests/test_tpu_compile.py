"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed here even without a chip: it compiles for a
described ``v5e:2x2`` topology, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM) — what interpret mode
cannot show.  Every case runs at minicpm-2b's published widths (d_model
2304, 36 heads of 64, d_ff 5760, vocab 122753 padded to 122880) and checks
that the compiled program holds a Pallas kernel (``tpu_custom_call``).

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


def test_paged_decode_attention_pallas_compiles(one_chip):
    rows = SLOTS * MAX_SEQ // PAGE + 1          # + the trash page
    _compile_has_kernel(
        lambda q, k, v, table, length: ops.paged_decode_attention(
            q, k, v, table, page_size=PAGE, length=length, backend="pallas"),
        one_chip, ((SLOTS, H, HD), BF16), ((rows, PAGE, H * HD), BF16),
        ((rows, PAGE, H * HD), BF16), ((SLOTS, MAX_SEQ // PAGE), I32),
        ((SLOTS,), I32))


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
