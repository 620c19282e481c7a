"""Pallas kernels (interpret mode) vs their pure-jnp oracles: shape/dtype
sweeps per kernel plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.loops import LegalityError
from repro.kernels import ops, ref
from repro.kernels.block_spmm import block_spmm_pallas, densify_to_bcsr
from repro.kernels.brgemm import brgemm_blocked_pallas, matmul_pallas, pick_tiles
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_decode_pallas)
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.paged_attention import paged_decode_pallas

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-1) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# BRGEMM / matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [(32, 64, 48), (64, 32, 128), (16, 16, 16)])
def test_matmul_shapes_dtypes(mkn, dtype):
    m, k, n = mkn
    a = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32), dtype)
    b = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32), dtype)
    out = matmul_pallas(a, b, tiles=(16, 16, 16), interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("activation", [None, "relu", "gelu", "silu"])
def test_matmul_fused_epilogue(activation):
    a = jnp.asarray(RNG.normal(size=(32, 32)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(32, 64)).astype(np.float32))
    bias = jnp.asarray(RNG.normal(size=(64,)).astype(np.float32))
    out = matmul_pallas(a, b, tiles=(16, 16, 32), bias=bias,
                        activation=activation, interpret=True)
    want = ref.matmul_ref(a, b, bias=bias, activation=activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("spec,bs", [
    ("bca", {}), ("cba", {}), ("bcba", {"b": (2,)}), ("bcaa", {"a": (2,)}),
    ("BCa", {}), ("cbca", {"c": (2,)}),
])
def test_matmul_spec_strings(spec, bs):
    a = jnp.asarray(RNG.normal(size=(64, 64)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(64, 64)).astype(np.float32))
    out = matmul_pallas(a, b, tiles=(16, 16, 16), spec_string=spec,
                        block_steps=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-3)


def test_matmul_rejects_non_innermost_reduction():
    a = jnp.zeros((32, 32)); b = jnp.zeros((32, 32))
    with pytest.raises(LegalityError):
        matmul_pallas(a, b, tiles=(16, 16, 16), spec_string="abc",
                      interpret=True)


def test_brgemm_blocked_paper_layout():
    A = jnp.asarray(RNG.normal(size=(4, 6, 8, 16)).astype(np.float32))
    B = jnp.asarray(RNG.normal(size=(3, 6, 16, 32)).astype(np.float32))
    out = brgemm_blocked_pallas(A, B, spec_string="bca", k_step=2,
                                interpret=True)
    want = ref.brgemm_blocked_ref(A, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_pick_tiles_vmem_budget():
    bm, bk, bn = pick_tiles(4096, 8192, 4096, jnp.bfloat16)
    assert 4096 % bm == 0 and 8192 % bk == 0 and 4096 % bn == 0
    assert 2 * (bm * bk + bk * bn) * 2 + bm * bn * 4 <= 96 * 2 ** 20


# ---------------------------------------------------------------------------
# Block-SpMM / grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("density", [0.0, 0.2, 0.7, 1.0])
@pytest.mark.parametrize("bm,bk", [(8, 8), (16, 16)])
def test_block_spmm_densities(density, bm, bk):
    m, k, n = 64, 64, 64
    dense = RNG.normal(size=(m, k)).astype(np.float32)
    tiles = dense.reshape(m // bm, bm, k // bk, bk).transpose(0, 2, 1, 3).copy()
    mask = RNG.random((m // bm, k // bk)) >= density
    tiles[mask] = 0
    dense = tiles.transpose(0, 2, 1, 3).reshape(m, k)
    blocks, rid, cid = densify_to_bcsr(dense, bm, bk)
    b = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    out = block_spmm_pallas(blocks, rid, cid, b, nrows_b=m // bm, bn=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), dense @ np.asarray(b),
                               rtol=1e-4, atol=1e-3)


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_block_spmm_random_patterns(seed):
    rng = np.random.default_rng(seed)
    m = k = 32
    bm = bk = 8
    dense = rng.normal(size=(m, k)).astype(np.float32)
    tiles = dense.reshape(4, 8, 4, 8).transpose(0, 2, 1, 3).copy()
    tiles[rng.random((4, 4)) < rng.uniform(0, 1)] = 0
    dense = tiles.transpose(0, 2, 1, 3).reshape(m, k)
    blocks, rid, cid = densify_to_bcsr(dense, bm, bk)
    b = jnp.asarray(rng.normal(size=(k, 16)).astype(np.float32))
    out = block_spmm_pallas(blocks, rid, cid, b, nrows_b=4, bn=16,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out), dense @ np.asarray(b),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(dtype):
    """Rows sorted by group, each block of rows through its group's
    matrix; rows past the groups come out zero; an empty group is fine."""
    T, d, f = 40, 32, 64
    sizes = np.array([7, 0, 20, 5], np.int32)
    x = jnp.asarray(RNG.normal(size=(T, d)).astype(np.float32), dtype)
    w = jnp.asarray(RNG.normal(size=(4, d, f)).astype(np.float32), dtype)
    out = ops.grouped_matmul(x, w, jnp.asarray(sizes), out_dtype=jnp.float32)
    gid = np.repeat(np.arange(4), sizes)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    want = np.zeros((T, f), np.float32)
    want[:len(gid)] = np.einsum("td,tdf->tf", xf[:len(gid)], wf[gid])
    np.testing.assert_allclose(np.asarray(out), want, **_tol(dtype))


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,hk", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("kwargs", [
    dict(causal=True), dict(causal=True, window=48), dict(causal=False)])
def test_flash_attention_gqa_masks(h, hk, kwargs):
    B, S, D = 2, 128, 32
    q = jnp.asarray(RNG.normal(size=(B, h, S, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, hk, S, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, hk, S, D)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, block_q=32, block_kv=32,
                                 interpret=True, **kwargs)
    want = ref.attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, H, S, D = 1, 2, 64, 16
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)).astype(np.float32), dtype)
    k = jnp.asarray(RNG.normal(size=(B, H, S, D)).astype(np.float32), dtype)
    v = jnp.asarray(RNG.normal(size=(B, H, S, D)).astype(np.float32), dtype)
    out = flash_attention_pallas(q, k, v, block_q=32, block_kv=32,
                                 interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_decode_lengths_and_window():
    B, H, Hk, S, D = 3, 4, 2, 128, 16
    q = jnp.asarray(RNG.normal(size=(B, H, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, Hk, S, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, Hk, S, D)).astype(np.float32))
    lens = jnp.asarray([40, 128, 77], jnp.int32)
    for window in (None, 32):
        out = flash_decode_pallas(q, k, v, length=lens, window=window,
                                  block_kv=32, interpret=True)
        want = ref.decode_attention_ref(q, k, v, length=lens, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pages_per_block", [None, 3],
                         ids=["derived", "3_of_8"])
@pytest.mark.parametrize("window", [None, 10], ids=["full", "window10"])
@pytest.mark.parametrize("h,hk,d,stack", [
    (2, 2, 64, None), (16, 1, 128, None), (8, 2, 64, None),
    (36, 36, 64, (3, 2)), (32, 2, 128, (4, 1)),
], ids=["mha_g1_d64", "gqa_g16_d128", "gqa_g4_d64",
        "mha_36x64_layer2_of3", "gqa_32_2x128_layer1_of4"])
def test_paged_decode_pallas_matches_reference(h, hk, d, stack, window,
                                               pages_per_block):
    """The paged kernel reads the live pages in place and agrees with the
    gather reference: lengths 1, one page, one page + 1, a partial last
    block and the whole table; shuffled, non-contiguous pages; one slot
    whose table is all trash page; blocks of 3 pages of 8 (a partial last
    block, and windowed slots that skip their first blocks).  With
    ``stack`` (layers, layer) the pools are stacked over a layer group
    (L, P, page, Hk·D) and the kernel reads the given layer of the stack:
    it must agree with the reference over that layer's pool alone."""
    ps, maxp = 8, 8
    lens = [1, ps, ps + 1, 21, maxp * ps, 13]
    b = len(lens)
    rows = b * maxp + 1                        # the last row is the trash page
    lead = (stack[0],) if stack else ()
    q = jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32))
    k_pool = jnp.asarray(
        RNG.normal(size=lead + (rows, ps, hk * d)).astype(np.float32))
    v_pool = jnp.asarray(
        RNG.normal(size=lead + (rows, ps, hk * d)).astype(np.float32))
    table = RNG.permutation(rows - 1)[:b * maxp].reshape(b, maxp)
    table[-1] = rows - 1
    table = jnp.asarray(table, jnp.int32)
    length = jnp.asarray(lens, jnp.int32)
    layer = {"layer": stack[1]} if stack else {}
    out = paged_decode_pallas(q, k_pool, v_pool, table, page_size=ps,
                              length=length, window=window,
                              pages_per_block=pages_per_block,
                              interpret=True, **layer)
    if stack:
        k_pool, v_pool = k_pool[stack[1]], v_pool[stack[1]]
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, table,
                                          page_size=ps, length=length,
                                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_attention_xla_chunked_matches_oracle():
    B, H, Hk, S, D = 2, 4, 2, 512, 16
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, Hk, S, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, Hk, S, D)).astype(np.float32))
    for kw in (dict(causal=True), dict(causal=True, window=64),
               dict(causal=False)):
        a = ref.attention_xla_chunked(q, k, v, block_q=128, **kw)
        b = ref.attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("block_kv", [64, 100])
def test_attention_xla_chunked_key_blocks_match_oracle(block_kv):
    """Keys in blocks with an online softmax (as rows past ``block_kv``
    are taken), a block that does not divide the keys, and a value width
    other than the query's (MLA's 192-wide q/k, 128-wide v)."""
    B, H, Hk, S, D, Dv = 1, 4, 2, 512, 24, 16
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, Hk, S, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, Hk, S, Dv)).astype(np.float32))
    for kw in (dict(causal=True), dict(causal=True, window=64),
               dict(causal=False)):
        a = ref.attention_xla_chunked(q, k, v, block_q=128,
                                      block_kv=block_kv, **kw)
        b = ref.attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# Mamba selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_mamba_scan_chunks(chunk):
    B, L, D, N = 2, 64, 16, 8
    x = jnp.asarray(RNG.normal(size=(B, L, D)).astype(np.float32))
    dt = jnp.asarray((0.1 + RNG.random((B, L, D))).astype(np.float32))
    a = jnp.asarray((-RNG.random((D, N))).astype(np.float32))
    bi = jnp.asarray(RNG.normal(size=(B, L, N)).astype(np.float32))
    ci = jnp.asarray(RNG.normal(size=(B, L, N)).astype(np.float32))
    d = jnp.asarray(RNG.normal(size=(D,)).astype(np.float32))
    y, h = mamba_scan_pallas(x, dt, a, bi, ci, d, chunk=chunk, interpret=True)
    yr, hr = ref.mamba_scan_ref(x, dt, a, bi, ci, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_mamba_scan_state_continuation():
    """Splitting a sequence across two kernel calls with carried state must
    match one full pass (the decode contract)."""
    B, L, D, N = 1, 32, 8, 4
    args = [RNG.normal(size=(B, L, D)).astype(np.float32),
            (0.1 + RNG.random((B, L, D))).astype(np.float32),
            (-RNG.random((D, N))).astype(np.float32),
            RNG.normal(size=(B, L, N)).astype(np.float32),
            RNG.normal(size=(B, L, N)).astype(np.float32),
            RNG.normal(size=(D,)).astype(np.float32)]
    x, dt, a, bi, ci, d = map(jnp.asarray, args)
    y_full, h_full = ref.mamba_scan_ref(x, dt, a, bi, ci, d)
    h = None
    ys = []
    for sl in (slice(0, 16), slice(16, 32)):
        y, h = mamba_scan_pallas(x[:, sl], dt[:, sl], a, bi[:, sl],
                                 ci[:, sl], d, h0=h, chunk=8, interpret=True)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=1e-4)


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rs,stride", [((1, 1), 1), ((3, 3), 1), ((3, 3), 2)])
def test_conv2d_backends(rs, stride):
    r, s = rs
    x = jnp.asarray(RNG.normal(size=(2, 10, 10, 8)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(r, s, 8, 16)).astype(np.float32))
    with ops.use_backend("pallas_interpret"):
        out = ops.conv2d(x, w, stride=stride)
    want = ref.conv2d_ref(x, w, stride=stride)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_ops_backend_dispatch():
    a = jnp.ones((16, 16)); b = jnp.ones((16, 16))
    assert ops.current_backend() == "xla"
    with ops.use_backend("pallas_interpret"):
        assert ops.current_backend() == "pallas_interpret"
        out = ops.matmul(a, b, tiles=(8, 8, 8))
    np.testing.assert_allclose(np.asarray(out), 16.0)


# ---------------------------------------------------------------------------
# Fused output layer (paper Listing 6)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dropout", [0.0, 0.5])
def test_fused_output_layer(dtype, dropout):
    from repro.kernels.fused_output import (fused_output_pallas,
                                            fused_output_ref)
    m, k, n = 64, 128, 256
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32), dtype)
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32), dtype)
    bias = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    res = jnp.asarray(RNG.normal(size=(m, n)).astype(np.float32), dtype)
    gamma = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    beta = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    mask = jnp.asarray(RNG.random((m, n)) > dropout)
    out = fused_output_pallas(x, w, bias, res, gamma, beta, keep_mask=mask,
                              dropout_rate=dropout, bm=16, bk=32, bn=64,
                              interpret=True)
    want = fused_output_ref(x, w, bias, res, gamma, beta, keep_mask=mask,
                            dropout_rate=dropout)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
