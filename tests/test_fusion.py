"""TPP-chain fusion compiler: graph-vs-reference parity for every registered
epilogue TPP (fp32 + bf16), legality of norm epilogues vs. the nest's
innermost band, parity of the TppGraph fused-output reimplementation against
the hand-written kernel's oracle, multi-root graphs (gated MLP / fused QKV /
attn-out) vs their unfused ``ops.matmul`` compositions, the graph
simplification pass, and the compile/tune caches."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fusion
from repro.core import perf_model
from repro.fusion.graph import EPILOGUE_OPS

RNG = np.random.default_rng(7)
M, K, N = 32, 64, 128
TILES = (16, 32, 64)


def _tol(dtype):
    # fp32: 1e-5 (contraction blocking order is the only difference);
    # bf16: 2e-2 relative (bf16 inputs, fp32 accumulate/epilogue)
    return (dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32
            else dict(rtol=2e-2, atol=2e-1))


def _operands_for(graph, dtype, m=M, k=K, n=N):
    """Random call-time operands for every operand kind of ``graph``."""
    ops = {}
    for spec in graph.operands:
        if spec.kind == "lhs":
            v = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32), dtype)
        elif spec.kind == "rhs":
            v = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32), dtype)
        elif spec.kind == "tile":
            v = jnp.asarray(RNG.normal(size=(m, n)).astype(np.float32), dtype)
        elif spec.kind == "mask":
            v = jnp.asarray(RNG.random((m, n)) > 0.4)
        elif spec.kind == "scalar":   # PRNG seed
            v = jnp.asarray(int(RNG.integers(0, 2**31)), jnp.uint32)
        else:  # rowvec — fp32 like the model's norm/bias params
            v = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
        ops[spec.name] = v
    return ops


def _single_op_graph(op_name):
    """matmul → <op> with whatever operands the op needs."""
    op = EPILOGUE_OPS[op_name]
    operands = [("x", "lhs"), ("w", "rhs")]
    extra = []
    for i, kind in enumerate(op.operand_kinds):
        nm = f"p{i}"
        operands.append((nm, kind))
        extra.append(nm)
    attrs = ({"rate": 0.3} if op_name in ("dropout", "dropout_grad") else
             {"rate": 0.3, "salt": 11} if op_name in ("dropout_rng",
                                                      "dropout_rng_grad")
             else {"s": 0.5} if op_name == "scale" else {})
    # value inputs beyond the accumulator become (M, N) tile operands
    # ("acc", "y0", "y1", ...) — covers binary TPPs and the derivative ops
    values = ["acc"]
    for i in range(op.value_arity - 1):
        operands.append((f"y{i}", "tile"))
        values.append(f"y{i}")
    return fusion.TppGraph(
        name=f"g_{op_name}",
        operands=tuple(fusion.OperandSpec(n, k) for n, k in operands),
        nodes=(fusion.Node(f"n_{op_name}", op_name, (*values, *extra),
                           tuple(sorted(attrs.items()))),),
    )


# ---------------------------------------------------------------------------
# Parity: every registered epilogue op, both dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op_name", sorted(EPILOGUE_OPS))
def test_epilogue_op_parity(op_name, dtype):
    g = _single_op_graph(op_name)
    ops = _operands_for(g, dtype)
    ref = fusion.compile(g, path="xla", out_dtype=jnp.float32)(**ops)
    pal = fusion.compile(g, path="pallas", tiles=TILES, interpret=True,
                         out_dtype=jnp.float32)(**ops)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), **_tol(dtype))


@pytest.mark.parametrize("spec", ["bca", "bcca", "bbca", "bcaa"])
def test_norm_graph_spec_sweep(spec):
    """Blocked/multi-level schedules with N inside M all agree for a
    layernorm-terminated graph (panel + statistics generalize)."""
    bs = {"c": (2,)} if "cc" in spec else ({"b": (2,)} if "bb" in spec
                                           else ({"a": (2,)} if "aa" in spec else {}))
    g = fusion.fused_output_graph(0.0)
    ops = _operands_for(g, jnp.float32)
    ref = fusion.compile(g, path="xla")(**ops)
    pal = fusion.compile(g, path="pallas", tiles=TILES, spec_string=spec,
                         block_steps=bs, interpret=True)(**ops)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The showcase graphs: fused-output (Listing 6) and fused-MLP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dropout", [0.0, 0.5])
def test_fused_output_graph_matches_handwritten_ref(dtype, dropout):
    from repro.kernels.fused_output import fused_output_ref
    m, k, n = 64, 128, 256
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32), dtype)
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32), dtype)
    bias = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    res = jnp.asarray(RNG.normal(size=(m, n)).astype(np.float32), dtype)
    gamma = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    beta = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    mask = jnp.asarray(RNG.random((m, n)) > dropout)

    out = fusion.fused_output_apply(
        x, w, bias, res, gamma, beta, keep_mask=mask, dropout_rate=dropout,
        backend="pallas_interpret", tiles=(16, 32, 64))
    want = fused_output_ref(x, w, bias, res, gamma, beta, keep_mask=mask,
                            dropout_rate=dropout)
    tol = (dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32
           else dict(rtol=2e-2, atol=2e-1))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["gelu", "relu"])
def test_fused_mlp_graph_parity(dtype, act):
    g = fusion.fused_mlp_graph(act)
    ops = _operands_for(g, dtype, m=64, k=64, n=128)
    ref = fusion.compile(g, path="xla")(**ops)
    pal = fusion.compile(g, path="pallas", tiles=(16, 32, 64),
                         interpret=True)(**ops)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_mlp_block_use_fusion_flag_matches_unfused():
    """models.blocks.mlp_apply routed through the fusion subsystem (config
    flag) equals the direct ops.matmul path."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import blocks

    cfg = get_config("bert_large").reduced()
    cfg = dataclasses.replace(cfg, gated_mlp=False, mlp_activation="gelu")
    key = __import__("jax").random.PRNGKey(0)
    p = blocks.init_mlp(cfg, key)
    x2d = jnp.asarray(RNG.normal(size=(16, cfg.d_model)).astype(np.float32))
    y0 = blocks.mlp_apply(cfg, p, x2d)
    y1 = blocks.mlp_apply(dataclasses.replace(cfg, use_fusion=True), p, x2d)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Multi-root graphs: gated MLP, fused QKV, attention output projection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_fused_gated_mlp_parity(path, dtype, act):
    """act(x@wg) * (x@wu) as ONE two-root nest vs the unfused ops.matmul
    composition, both lowering paths, both dtypes."""
    from repro.kernels import ops as kops
    g = fusion.fused_gated_mlp_graph(act)
    opd = _operands_for(g, dtype, m=32, k=64, n=128)
    kw = dict(tiles=TILES, interpret=True) if path == "pallas" else {}
    out = fusion.compile(g, path=path, out_dtype=jnp.float32, **kw)(**opd)
    a = kops.matmul(opd["x"], opd["wg"], activation=act,
                    out_dtype=jnp.float32, backend="xla")
    u = kops.matmul(opd["x"], opd["wu"], out_dtype=jnp.float32, backend="xla")
    want = a * u
    tol = (dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32
           else _tol(dtype))   # fp32: blocking-order noise through the act
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **tol)


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_qkv_parity(path, dtype):
    """One lhs, three rhs, stacked (3, M, N) output vs three ops.matmul."""
    from repro.kernels import ops as kops
    g = fusion.fused_qkv_graph()
    opd = _operands_for(g, dtype, m=32, k=64, n=128)
    kw = dict(tiles=TILES, interpret=True) if path == "pallas" else {}
    out = fusion.compile(g, path=path, out_dtype=jnp.float32, **kw)(**opd)
    assert out.shape == (3, 32, 128)
    want = jnp.stack([
        kops.matmul(opd["x"], opd[w], out_dtype=jnp.float32, backend="xla")
        for w in ("wq", "wk", "wv")
    ])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **_tol(dtype))


@pytest.mark.parametrize("norm", ["", "layernorm", "rmsnorm"])
def test_fused_attn_out_graph_parity(norm):
    """Output projection + residual (+ norm): multi-operand single-root tail,
    Pallas vs the composed reference."""
    g = fusion.fused_attn_out_graph(True, norm)
    opd = _operands_for(g, jnp.float32)
    ref = fusion.compile(g, path="xla")(**opd)
    pal = fusion.compile(g, path="pallas", tiles=TILES, interpret=True)(**opd)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spec", ["bca", "bcca", "bbca", "bcaa", "cba"])
def test_gated_mlp_spec_sweep(spec):
    """Multi-root graphs have no reducing epilogue here, so blocked and even
    N-outer schedules are legal — and all agree."""
    bs = {"c": (2,)} if "cc" in spec else ({"b": (2,)} if "bb" in spec
                                           else ({"a": (2,)} if "aa" in spec else {}))
    g = fusion.fused_gated_mlp_graph("silu")
    opd = _operands_for(g, jnp.float32)
    ref = fusion.compile(g, path="xla")(**opd)
    pal = fusion.compile(g, path="pallas", tiles=TILES, spec_string=spec,
                         block_steps=bs, interpret=True)(**opd)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_multi_root_shared_lhs_mapped_once():
    """The shared activation operand appears once in the packed operand order
    and once in the nest's TensorMaps (one HBM fetch stream, R MXU issues)."""
    g = fusion.fused_qkv_graph()
    assert [o.name for o in g.contraction_operands] == ["x", "wq", "wk", "wv"]
    loops, in_maps, out_map = fusion.lowering.build_nest_inputs(
        g, M, K, N, TILES)
    assert len(in_maps) == 4                      # x mapped once, not thrice
    assert out_map.letters == (None, "b", "c")    # stacked (3, M, N) output
    assert out_map.tile[0] == 3


def test_mlp_block_gated_use_fusion_flag_matches_unfused():
    """models.blocks.mlp_apply gated path routed through the two-root graph
    (config flag) equals the direct ops.matmul composition."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import blocks

    cfg = get_config("llama2_13b").reduced()
    assert cfg.gated_mlp
    key = __import__("jax").random.PRNGKey(0)
    p = blocks.init_mlp(cfg, key)
    x2d = jnp.asarray(RNG.normal(size=(16, cfg.d_model)).astype(np.float32))
    y0 = blocks.mlp_apply(cfg, p, x2d)
    y1 = blocks.mlp_apply(dataclasses.replace(cfg, use_fusion=True), p, x2d)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32),
                               rtol=2e-2, atol=2e-1)


def test_attention_use_fusion_flag_matches_unfused():
    """attention_apply's output projection through fused_attn_out_graph."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import blocks

    cfg = get_config("llama2_13b").reduced()
    key = __import__("jax").random.PRNGKey(1)
    p = blocks.init_attention(cfg, key)
    x = jnp.asarray(RNG.normal(
        size=(2, 8, cfg.d_model)).astype(np.float32))
    y0, _ = blocks.attention_apply(cfg, p, x)
    y1, _ = blocks.attention_apply(
        dataclasses.replace(cfg, use_fusion=True), p, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32),
                               rtol=2e-2, atol=2e-1)


# ---------------------------------------------------------------------------
# Graph simplification pass
# ---------------------------------------------------------------------------

def test_simplify_drops_identity_and_rate0_dropout():
    g = fusion.TppGraph.chain(
        "simp",
        [("identity", (), {}),
         ("dropout", ("keep_mask",), {"rate": 0.0}),
         ("bias_add", ("bias",), {})],
        [("x", "lhs"), ("w", "rhs"), ("keep_mask", "mask"),
         ("bias", "rowvec")],
    )
    s = fusion.simplify_graph(g)
    assert [nd.op for nd in s.nodes] == ["bias_add"]
    assert "keep_mask" not in s.operand_names
    assert s.nodes[0].inputs[0] == "acc"      # rewired through dropped nodes


def test_simplify_is_identity_on_clean_graphs():
    g = fusion.fused_output_graph(0.5)
    assert fusion.simplify_graph(g) is g
    g2 = fusion.fused_gated_mlp_graph("silu")
    assert fusion.simplify_graph(g2) is g2


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_simplification_invariance(path):
    """compile(simplified) == compile(original) — and the original call
    signature (incl. the dropped mask) keeps working."""
    g = fusion.fused_output_graph(0.0)
    opd = _operands_for(g, jnp.float32)        # includes the PRNG seed
    kw = dict(tiles=TILES, interpret=True) if path == "pallas" else {}
    out = fusion.compile(g, path=path, **kw)(**opd)
    raw = fusion.compile(g, path=path, simplify=False, **kw)(**opd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(raw),
                               rtol=1e-6, atol=1e-6)
    # same result without the seed operand at all
    opd2 = {k: v for k, v in opd.items() if k != "seed"}
    out2 = fusion.compile(g, path=path, **kw)(**opd2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=0, atol=0)


def test_rate0_fused_output_has_no_mask_tensormap():
    """Acceptance: rate-0 fused_output lowers with no dropout operand in its
    TensorMaps (neither a mask nor a seed)."""
    g = fusion.simplify_graph(fusion.fused_output_graph(0.0))
    assert "seed" not in g.operand_names
    loops, in_maps, out_map = fusion.lowering.build_nest_inputs(
        g, M, K, N, TILES)
    # x, w, bias, residual, gamma, beta — and nothing (M, N)-boolean
    assert len(in_maps) == 6
    g1 = fusion.simplify_graph(fusion.fused_output_graph(0.1))
    assert "seed" in g1.operand_names


def test_rng_fused_output_streams_no_mask_at_any_rate():
    """Acceptance: at rate > 0 the PRNG graph lowers with NO (M, N) mask
    operand — the seed is the only dropout input and it is one element —
    confirmed structurally and by ``graph_cost`` traffic accounting."""
    g_rng = fusion.simplify_graph(fusion.fused_output_graph(0.1))
    assert all(o.kind != "mask" for o in g_rng.operands)
    loops, in_maps, out_map = fusion.lowering.build_nest_inputs(
        g_rng, M, K, N, TILES)
    seed_pos = [i for i, o in enumerate(
        g_rng.contraction_operands + g_rng.epilogue_operands)
        if o.kind == "scalar"]
    assert len(seed_pos) == 1 and in_maps[seed_pos[0]].tile == (1, 1)
    # traffic: the legacy mask graph moves >= M*N more bytes per call
    g_mask = fusion.fused_output_graph(0.1, rng_dropout=False)
    rep_mask = fusion.graph_cost(g_mask, 256, 256, 256, tiles=(32, 64, 64),
                                 dtype=np.float32)
    rep_rng = fusion.graph_cost(fusion.fused_output_graph(0.1), 256, 256,
                                256, tiles=(32, 64, 64), dtype=np.float32)
    assert rep_mask.hbm_bytes - rep_rng.hbm_bytes >= 256 * 256
    # ...while the PRNG graph pays the bits-generation VPU flops instead
    assert rep_rng.compute_time >= rep_mask.compute_time


def test_fused_attn_out_apply_validates_norm_params():
    o = jnp.ones((16, 16), jnp.float32)
    wo = jnp.ones((16, 16), jnp.float32)
    gamma = jnp.ones((16,), jnp.float32)
    with pytest.raises(ValueError):            # norm without its params
        fusion.fused_attn_out_apply(o, wo, norm="rmsnorm", backend="xla")
    with pytest.raises(ValueError):            # params without a norm
        fusion.fused_attn_out_apply(o, wo, gamma=gamma, backend="xla")
    out = fusion.fused_attn_out_apply(o, wo, norm="rmsnorm", gamma=gamma,
                                      backend="xla")
    assert out.shape == (16, 16)


def _fused_output_args(dtype=jnp.float32, m=M, k=K, n=N):
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32), dtype)
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32), dtype)
    bias, gamma, beta = (jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
                         for _ in range(3))
    res = jnp.asarray(RNG.normal(size=(m, n)).astype(np.float32), dtype)
    return x, w, bias, res, gamma, beta


def test_fused_output_apply_requires_seed_only_when_dropping():
    args = _fused_output_args()
    out = fusion.fused_output_apply(*args, dropout_rate=0.0, backend="xla")
    assert out.shape == (M, N)
    with pytest.raises(ValueError, match="dropout_seed"):
        fusion.fused_output_apply(*args, dropout_rate=0.5, backend="xla")
    # a seed enables the in-kernel PRNG — no mask anywhere
    out_d = fusion.fused_output_apply(*args, dropout_rate=0.5,
                                      dropout_seed=7, backend="xla")
    assert out_d.shape == (M, N)
    assert not np.allclose(np.asarray(out_d), np.asarray(out))


def test_fused_output_apply_deterministic_escape():
    """Satellite bugfix: inference calls at rate > 0 no longer demand a
    mask/seed — deterministic=True simplifies the dropout node away and
    matches the rate-0 result exactly."""
    args = _fused_output_args()
    for backend in ("xla", "pallas_interpret"):
        out0 = fusion.fused_output_apply(*args, dropout_rate=0.0,
                                         backend=backend)
        out_det = fusion.fused_output_apply(*args, dropout_rate=0.5,
                                            deterministic=True,
                                            backend=backend)
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out_det))


def test_fused_output_apply_legacy_mask_still_works():
    """Backward compat: passing keep_mask routes through the registered
    mask-operand ``dropout`` op (same semantics as before the PRNG)."""
    args = _fused_output_args()
    mask = jnp.asarray(RNG.random((M, N)) > 0.5)
    outs = [np.asarray(fusion.fused_output_apply(
        *args, dropout_rate=0.5, keep_mask=mask, backend=be))
        for be in ("xla", "pallas_interpret")]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_fused_output_rng_backend_and_schedule_bit_identical_draws():
    """Acceptance: the counter-based draw is bit-identical across xla /
    pallas_interpret and across tuned schedules — compare the post-dropout
    zero pattern of a bare GEMM→dropout_rng graph (exact, not tolerance)."""
    g = fusion.TppGraph.chain(
        "g_rng_sched", [("dropout_rng", ("seed",), {"rate": 0.4, "salt": 5})],
        [("x", "lhs"), ("w", "rhs"), ("seed", "scalar")])
    ops = _operands_for(g, jnp.float32)
    ref = np.asarray(fusion.compile(g, path="xla",
                                    out_dtype=jnp.float32)(**ops))
    outs = [ref]
    for spec, bs, tiles in [("bca", {}, TILES), ("cba", {}, TILES),
                            ("bcca", {"c": (2,)}, TILES),
                            ("bbca", {"b": (2,)}, (8, 32, 32)),
                            ("cbba", {"b": (2,)}, (8, 16, 64))]:
        outs.append(np.asarray(fusion.compile(
            g, path="pallas", tiles=tiles, spec_string=spec, block_steps=bs,
            interpret=True, out_dtype=jnp.float32)(**ops)))
    for o in outs[1:]:
        np.testing.assert_array_equal(o == 0.0, ref == 0.0)
        np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-5)
    # a different seed flips decisions
    ops2 = dict(ops, seed=ops["seed"] + jnp.uint32(1))
    other = np.asarray(fusion.compile(g, path="xla",
                                      out_dtype=jnp.float32)(**ops2))
    assert ((other == 0.0) != (ref == 0.0)).any()


@pytest.mark.parametrize("op_name", ["dropout", "dropout_rng"])
def test_dropout_bf16_rescale_runs_fp32(op_name):
    """Satellite bugfix pin: the 1/(1-rate) rescale (and the PRNG keep
    decision) run in fp32 — at rate 0.5 the survivor values of a bf16 graph
    must equal exactly bf16(fp32_value * 2), with zero tolerance."""
    rate = 0.5
    if op_name == "dropout_rng":
        attrs = {"rate": rate, "salt": 3}
        extra = [("seed", "scalar")]
    else:
        attrs = {"rate": rate}
        extra = [("keep_mask", "mask")]
    g = fusion.TppGraph.chain(
        f"g_bf16_{op_name}", [(op_name, tuple(n for n, _ in extra), attrs)],
        [("x", "lhs"), ("w", "rhs"), *extra])
    ops = _operands_for(g, jnp.bfloat16)
    base = fusion.TppGraph.chain(
        "g_bf16_base", [], [("x", "lhs"), ("w", "rhs")])
    for path, kw in (("xla", {}), ("pallas", dict(tiles=TILES,
                                                  interpret=True))):
        out = np.asarray(fusion.compile(g, path=path, **kw)(**ops),
                         np.float32)
        raw = np.asarray(fusion.compile(base, path=path,
                                        out_dtype=jnp.float32, **kw)(
            x=ops["x"], w=ops["w"]), np.float32)
        want = np.asarray(jnp.asarray(raw * 2.0).astype(jnp.bfloat16),
                          np.float32)
        kept = out != 0.0
        np.testing.assert_array_equal(out[kept], want[kept])


# ---------------------------------------------------------------------------
# Compile memoization
# ---------------------------------------------------------------------------

def test_compile_for_backend_memoizes():
    g = fusion.fused_gated_mlp_graph("silu")
    f1 = fusion.compile_for_backend(g, "xla")
    f2 = fusion.compile_for_backend(g, "xla")
    assert f1 is f2
    f3 = fusion.compile_for_backend(g, "pallas_interpret", tiles=TILES)
    f4 = fusion.compile_for_backend(g, "pallas_interpret", tiles=TILES)
    assert f3 is f4 and f3 is not f1
    # dict-valued kwargs are frozen into the key, not a TypeError
    f5 = fusion.compile_for_backend(
        g, "pallas_interpret", tiles=TILES, block_steps={"b": (2,)})
    assert f5 is fusion.compile_for_backend(
        g, "pallas_interpret", tiles=TILES, block_steps={"b": (2,)})


# ---------------------------------------------------------------------------
# Legality
# ---------------------------------------------------------------------------

def test_multi_root_validation_errors():
    x, wq, wk = (fusion.OperandSpec("x", "lhs"), fusion.OperandSpec("wq", "rhs"),
                 fusion.OperandSpec("wk", "rhs"))
    with pytest.raises(fusion.FusionLegalityError):
        # duplicate root names
        fusion.TppGraph("bad_dup", (x, wq, wk),
                        roots=(fusion.ContractionRoot("q", "x", "wq"),
                               fusion.ContractionRoot("q", "x", "wk")))
    with pytest.raises(fusion.FusionLegalityError):
        # epilogue references an unknown root ("acc" is single-root-only)
        fusion.TppGraph("bad_acc", (x, wq, wk),
                        roots=(fusion.ContractionRoot("q", "x", "wq"),
                               fusion.ContractionRoot("k", "x", "wk")),
                        nodes=(fusion.Node("n0", "relu", ("acc",)),))
    with pytest.raises(fusion.FusionLegalityError):
        # reducing node with multi-root (stacked) output
        fusion.TppGraph("bad_norm", (x, wq, wk),
                        roots=(fusion.ContractionRoot("q", "x", "wq"),
                               fusion.ContractionRoot("k", "x", "wk")),
                        nodes=(fusion.Node("n0", "softmax", ("q",)),),
                        outputs=("n0", "k"))
    with pytest.raises(fusion.FusionLegalityError):
        # root wired to an operand of the wrong kind
        fusion.TppGraph("bad_kind", (x, wq, wk),
                        roots=(fusion.ContractionRoot("q", "wq", "x"),))
    with pytest.raises(fusion.FusionLegalityError):
        # rhs operand not referenced by any root
        fusion.TppGraph("bad_orphan", (x, wq, wk),
                        roots=(fusion.ContractionRoot("q", "x", "wq"),))
    with pytest.raises(fusion.FusionLegalityError):
        # unknown output name
        fusion.TppGraph("bad_out", (x, wq),
                        roots=(fusion.ContractionRoot("q", "x", "wq"),),
                        outputs=("nope",))


def test_multi_root_rejects_mismatched_shapes():
    g = fusion.fused_gated_mlp_graph("silu")
    x = jnp.zeros((32, 64), jnp.float32)
    wg = jnp.zeros((64, 128), jnp.float32)
    wu = jnp.zeros((64, 256), jnp.float32)   # different N
    with pytest.raises(fusion.FusionLegalityError):
        fusion.compile(g, path="pallas", tiles=TILES,
                       interpret=True)(x=x, wg=wg, wu=wu)
    # distinct lhs operands with mismatched K must be rejected too, not
    # silently read out of bounds
    g2 = fusion.TppGraph(
        "two_lhs",
        (fusion.OperandSpec("x1", "lhs"), fusion.OperandSpec("x2", "lhs"),
         fusion.OperandSpec("w", "rhs")),
        roots=(fusion.ContractionRoot("a1", "x1", "w"),
               fusion.ContractionRoot("a2", "x2", "w")),
        nodes=(fusion.Node("n0", "add", ("a1", "a2")),),
    )
    with pytest.raises(fusion.FusionLegalityError):
        fusion.compile(g2, path="pallas", tiles=TILES, interpret=True)(
            x1=jnp.zeros((32, 64), jnp.float32),
            x2=jnp.zeros((32, 32), jnp.float32),   # wrong K
            w=jnp.zeros((64, 128), jnp.float32))


def test_outputs_must_name_computed_values():
    x, w, r = (fusion.OperandSpec("x", "lhs"), fusion.OperandSpec("w", "rhs"),
               fusion.OperandSpec("r", "tile"))
    with pytest.raises(fusion.FusionLegalityError):
        fusion.TppGraph("bad_operand_out", (x, w, r),
                        nodes=(fusion.Node("n0", "residual_add", ("acc", "r")),),
                        outputs=("n0", "r"))
    # a no-op node forwarding an operand INTO an output is kept by the
    # simplifier (dropping it would leave an operand-named output)
    g = fusion.TppGraph(
        "id_out", (x, w, r),
        nodes=(fusion.Node("n0", "identity", ("r",)),
               fusion.Node("n1", "add", ("acc", "n0"))),
        outputs=("n1", "n0"))
    s = fusion.simplify_graph(g)
    assert "n0" in [nd.name for nd in s.nodes]
    opd = {"x": jnp.ones((16, 16), jnp.float32),
           "w": jnp.ones((16, 16), jnp.float32),
           "r": jnp.full((16, 16), 2.0, jnp.float32)}
    out = fusion.compile(g, path="pallas", tiles=(16, 16, 16),
                         interpret=True)(**opd)
    ref = fusion.compile(g, path="xla")(**opd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(out[1]), 2.0)
    # a no-op aliasing another OUTPUT is kept too (dropping it would rewrite
    # outputs to a duplicate pair and fail validation on rebuild)
    ga = fusion.TppGraph(
        "alias_out", (x, w),
        nodes=(fusion.Node("n0", "relu", ("acc",)),
               fusion.Node("n1", "identity", ("n0",))),
        outputs=("n0", "n1"))
    sa = fusion.simplify_graph(ga)
    assert sa.outputs == ("n0", "n1")
    opd2 = {"x": opd["x"], "w": opd["w"]}
    outa = fusion.compile(ga, path="pallas", tiles=(16, 16, 16),
                          interpret=True)(**opd2)
    np.testing.assert_allclose(np.asarray(outa[0]), np.asarray(outa[1]))
    # and the cost path accepts it (it simplifies unconditionally)
    fusion.graph_cost(ga, 16, 16, 16, tiles=(16, 16, 16), dtype=np.float32)


def test_norm_epilogue_rejects_n_outside_innermost_band():
    g = fusion.fused_output_graph(0.0)
    ops = _operands_for(g, jnp.float32)
    # N outside M: row statistics would close before the row completes
    with pytest.raises(fusion.FusionLegalityError):
        fusion.compile(g, path="pallas", tiles=TILES, spec_string="cba",
                       interpret=True)(**ops)


def test_norm_epilogue_rejects_parallel_n():
    g = fusion.fused_output_graph(0.0)
    ops = _operands_for(g, jnp.float32)
    with pytest.raises(fusion.FusionLegalityError):
        fusion.compile(g, path="pallas", tiles=TILES, spec_string="bCa",
                       interpret=True)(**ops)


def test_operand_declaration_order_is_irrelevant():
    """Operands declared in any order (lhs/rhs last) lower identically —
    the Pallas path packs canonically, not by declaration position."""
    g = fusion.TppGraph(
        name="reordered",
        operands=(fusion.OperandSpec("r", "tile"),
                  fusion.OperandSpec("w", "rhs"),
                  fusion.OperandSpec("x", "lhs")),
        nodes=(fusion.Node("n0", "residual_add", ("acc", "r")),),
    )
    m = k = n = 32
    ops = {
        "x": jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32)),
        "w": jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32)),
        "r": jnp.asarray(RNG.normal(size=(m, n)).astype(np.float32)),
    }
    ref = fusion.compile(g, path="xla")(**ops)
    pal = fusion.compile(g, path="pallas", tiles=(16, 16, 16),
                         interpret=True)(**ops)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    want = np.asarray(ops["x"]) @ np.asarray(ops["w"]) + np.asarray(ops["r"])
    np.testing.assert_allclose(np.asarray(pal), want, rtol=1e-4, atol=1e-4)


def test_non_norm_graph_allows_n_outer():
    """Without a reducing epilogue 'cba' is a legal schedule."""
    g = fusion.fused_mlp_graph("relu")
    ops = _operands_for(g, jnp.float32, m=64, k=64, n=128)
    ref = fusion.compile(g, path="xla")(**ops)
    pal = fusion.compile(g, path="pallas", tiles=(16, 32, 64),
                         spec_string="cba", interpret=True)(**ops)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_reduction_not_innermost_still_rejected():
    g = fusion.fused_mlp_graph("relu")
    ops = _operands_for(g, jnp.float32, m=64, k=64, n=128)
    with pytest.raises(Exception):  # LegalityError from the K-innermost check
        fusion.compile(g, path="pallas", tiles=(16, 32, 64),
                       spec_string="abc", interpret=True)(**ops)


def test_graph_validation_errors():
    # pointwise nodes AFTER the reducing node are legal (post-reduce band:
    # they run on the finished full-row panel) …
    fusion.TppGraph(
        name="ok_postreduce",
        operands=(fusion.OperandSpec("x", "lhs"),
                  fusion.OperandSpec("w", "rhs")),
        nodes=(fusion.Node("n0", "softmax", ("acc",)),
               fusion.Node("n1", "relu", ("n0",))),
    )
    with pytest.raises(fusion.FusionLegalityError):
        # … but two reducing nodes in one graph are not
        fusion.TppGraph(
            name="bad0",
            operands=(fusion.OperandSpec("x", "lhs"),
                      fusion.OperandSpec("w", "rhs")),
            nodes=(fusion.Node("n0", "softmax", ("acc",)),
                   fusion.Node("n1", "softmax", ("n0",))),
        )
    with pytest.raises(fusion.FusionLegalityError):
        # … nor a post-reduce node reading a pre-reduce computed value that
        # is not staged (only the reducer's inputs stay panel-resident)
        fusion.TppGraph(
            name="bad0b",
            operands=(fusion.OperandSpec("x", "lhs"),
                      fusion.OperandSpec("w", "rhs")),
            nodes=(fusion.Node("n0", "relu", ("acc",)),
                   fusion.Node("n1", "softmax", ("acc",)),
                   fusion.Node("n2", "mul", ("n1", "n0"))),
        )
    with pytest.raises(fusion.FusionLegalityError):
        # rowvec op pointed at a tile operand
        fusion.TppGraph(
            name="bad2",
            operands=(fusion.OperandSpec("x", "lhs"),
                      fusion.OperandSpec("w", "rhs"),
                      fusion.OperandSpec("r", "tile")),
            nodes=(fusion.Node("n0", "bias_add", ("acc", "r")),),
        )
    with pytest.raises(fusion.FusionLegalityError):
        # unknown op
        fusion.TppGraph(
            name="bad3",
            operands=(fusion.OperandSpec("x", "lhs"),
                      fusion.OperandSpec("w", "rhs")),
            nodes=(fusion.Node("n0", "frobnicate", ("acc",)),),
        )


# ---------------------------------------------------------------------------
# Cost path
# ---------------------------------------------------------------------------

def test_graph_cost_counts_epilogue_traffic_and_flops():
    g = fusion.fused_output_graph(0.1)
    plain = fusion.fused_mlp_graph("relu")
    m, k, n = 256, 256, 256
    rep_full = fusion.graph_cost(g, m, k, n, tiles=(32, 64, 64),
                                 dtype=np.float32)
    rep_plain = fusion.graph_cost(plain, m, k, n, tiles=(32, 64, 64),
                                  dtype=np.float32)
    # the residual/mask operands add HBM traffic, the norm adds VPU time
    assert rep_full.hbm_bytes > rep_plain.hbm_bytes
    assert rep_full.compute_time > rep_plain.compute_time
    assert len(rep_full.fetches) == len(g.operands) + 1  # + output
    # rate-0 dropout: graph_cost prices the SIMPLIFIED graph — no mask map
    rep0 = fusion.graph_cost(fusion.fused_output_graph(0.0), m, k, n,
                             tiles=(32, 64, 64), dtype=np.float32)
    assert len(rep0.fetches) == len(rep_full.fetches) - 1


def test_autotune_graph_returns_legal_ranked_schedules():
    g = fusion.fused_output_graph(0.0)
    results = fusion.autotune_graph(g, 128, 128, 256, tiles=(16, 32, 64),
                                    max_candidates=60)
    assert results, "no legal fused schedules found"
    scores = [r.score for r in results]
    assert scores == sorted(scores, reverse=True)
    for r in results:
        # every surviving schedule must actually lower + run
        out = fusion.compile(
            g, path="pallas", tiles=(16, 32, 64),
            interpret=True, **fusion.schedule_kwargs(r.candidate),
        )(**_operands_for(g, jnp.float32, 128, 128, 256))
        assert out.shape == (128, 256)


def test_autotune_graph_multi_root_ranks_and_caches():
    """End-to-end tuning of a two-root graph: legal ranked schedules that all
    lower+run, and a tune-cache hit on the second identical-signature call."""
    import tempfile
    g = fusion.fused_gated_mlp_graph("silu")
    with tempfile.TemporaryDirectory() as cd:
        results, stats = fusion.autotune_graph(
            g, 128, 128, 256, tiles=(16, 32, 64), max_candidates=60,
            cache_dir=cd, return_stats=True)
        assert results and not stats.cache_hit
        scores = [r.score for r in results]
        assert scores == sorted(scores, reverse=True)
        out = fusion.compile(
            g, path="pallas", tiles=(16, 32, 64), interpret=True,
            **fusion.schedule_kwargs(results[0].candidate),
        )(**_operands_for(g, jnp.float32, 128, 128, 256))
        assert out.shape == (128, 256)
        # identical signature → persistent-cache hit, same ranking
        again, stats2 = fusion.autotune_graph(
            g, 128, 128, 256, tiles=(16, 32, 64), max_candidates=60,
            cache_dir=cd, return_stats=True)
        assert stats2.cache_hit and stats2.candidates_generated == 0
        assert [r.candidate.spec_string for r in again[:5]] == \
            [r.candidate.spec_string for r in results[:5]]
        # a different root structure over the same operand kinds is a
        # different signature → miss
        g1 = fusion.fused_qkv_graph()
        _res3, stats3 = fusion.autotune_graph(
            g1, 128, 128, 256, tiles=(16, 32, 64), max_candidates=60,
            cache_dir=cd, return_stats=True)
        assert not stats3.cache_hit


def test_graph_signature_distinguishes_roots_and_outputs():
    g2 = fusion.fused_gated_mlp_graph("silu")
    g3 = fusion.fused_qkv_graph()
    g1 = fusion.fused_mlp_graph("gelu")
    sigs = {fusion.graph_signature(g) for g in (g1, g2, g3)}
    assert len(sigs) == 3


def test_graph_signature_keys_dropout_rate_and_scheme():
    """Satellite audit: the dropout rate keys tune-cache entries for BOTH
    dropout ops (a rate-0 graph simplifies to a different structure than a
    rate-0.1 one, and rate 0.1 vs 0.2 differ via node attrs), the PRNG
    graphs carry the bit-generation scheme, and mask vs PRNG graphs can
    never collide."""
    def sig(g):
        return fusion.graph_signature(fusion.simplify_graph(g))

    for rng_dropout in (True, False):
        sigs = {sig(fusion.fused_output_graph(r, rng_dropout=rng_dropout))
                for r in (0.0, 0.1, 0.2)}
        assert len(sigs) == 3, rng_dropout
    assert sig(fusion.fused_output_graph(0.1)) != sig(
        fusion.fused_output_graph(0.1, rng_dropout=False))
    from repro.fusion import rng as frng
    assert f"rng:{frng.SCHEME}" in sig(fusion.fused_output_graph(0.1))
    assert "rng:" not in sig(fusion.fused_output_graph(0.1,
                                                       rng_dropout=False))
    # salt is part of the identity too (two dropout sites ≠ one site)
    assert sig(fusion.fused_output_graph(0.1, dropout_salt=1)) != sig(
        fusion.fused_output_graph(0.1, dropout_salt=2))


def test_cross_rate_autotune_cache_miss():
    """Satellite: a schedule tuned at one dropout rate must MISS the cache
    at another rate — for the PRNG graph and the legacy mask graph alike."""
    import tempfile
    with tempfile.TemporaryDirectory() as cd:
        for rng_dropout in (True, False):
            g1 = fusion.fused_output_graph(0.1, rng_dropout=rng_dropout)
            g2 = fusion.fused_output_graph(0.2, rng_dropout=rng_dropout)
            g0 = fusion.fused_output_graph(0.0, rng_dropout=rng_dropout)
            _r, s1 = fusion.autotune_graph(
                g1, 128, 128, 256, tiles=(16, 32, 64), max_candidates=12,
                cache_dir=cd, return_stats=True)
            _r, s1b = fusion.autotune_graph(
                g1, 128, 128, 256, tiles=(16, 32, 64), max_candidates=12,
                cache_dir=cd, return_stats=True)
            _r, s2 = fusion.autotune_graph(
                g2, 128, 128, 256, tiles=(16, 32, 64), max_candidates=12,
                cache_dir=cd, return_stats=True)
            _r, s0 = fusion.autotune_graph(
                g0, 128, 128, 256, tiles=(16, 32, 64), max_candidates=12,
                cache_dir=cd, return_stats=True)
            assert not s1.cache_hit and s1b.cache_hit, rng_dropout
            assert not s2.cache_hit, rng_dropout      # rate 0.1 ≠ 0.2
            assert not s0.cache_hit, rng_dropout      # simplified ≠ rate>0


def test_multi_root_graph_cost_scales_flops_and_shares_lhs():
    """Two roots double the MXU work but the shared lhs is fetched once: the
    fused two-root nest moves fewer bytes than 2x the single-GEMM nest."""
    g2 = fusion.fused_gated_mlp_graph("silu")
    g1 = fusion.fused_attn_out_graph()          # bare single GEMM
    m = k = n = 256
    rep2 = fusion.graph_cost(g2, m, k, n, tiles=(32, 64, 64), dtype=np.float32)
    rep1 = fusion.graph_cost(g1, m, k, n, tiles=(32, 64, 64), dtype=np.float32)
    ep = g2.epilogue_flops_per_elem() * m * n
    assert rep2.flops == pytest.approx(2 * (rep1.flops) + ep)
    assert rep2.hbm_bytes < 2 * rep1.hbm_bytes
    unf = fusion.estimate_unfused(g2, m, k, n, dtype=np.float32,
                                  tiles=(32, 64, 64))
    assert rep2.hbm_bytes < unf.hbm_bytes


def test_estimate_unfused_charges_roundtrips():
    g = fusion.fused_output_graph(0.0)
    m, k, n = 1024, 1024, 1024
    unf = fusion.estimate_unfused(g, m, k, n, dtype=np.float32)
    # each epilogue op pays at least an (M,N) read+write
    assert unf.hbm_bytes > (m * k + k * n + m * n) * 4
    assert unf.epilogue_time > 0
    # schedule-aware comparison: same tiles and spec for both sides
    unf = fusion.estimate_unfused(g, m, k, n, dtype=np.float32,
                                  tiles=(128, 256, 128))
    rep = fusion.graph_cost(g, m, k, n, tiles=(128, 256, 128),
                            dtype=np.float32)
    # fusion saves HBM traffic at size on the Bert-Output-like shape
    assert rep.hbm_bytes < unf.hbm_bytes


def test_perf_model_epilogue_flops_param():
    """core.perf_model.predict's fused-epilogue VPU term is additive."""
    from repro.core.loops import LoopSpec, ThreadedLoop
    from repro.core.pallas_lowering import TensorMap

    loops = [LoopSpec(0, 4, 1, name="K"), LoopSpec(0, 4, 1, name="M"),
             LoopSpec(0, 4, 1, name="N")]
    tl = ThreadedLoop(loops, "bca", reduction_letters=("a",))
    in_maps = [TensorMap(("b", "a"), (32, 32), layout="flat"),
               TensorMap(("a", "c"), (32, 32), layout="flat")]
    out_map = TensorMap(("b", "c"), (32, 32), layout="flat")
    base = perf_model.predict(tl.nest, in_maps, out_map, dtype=np.float32,
                              flops_per_body=2 * 32 ** 3)
    fused = perf_model.predict(tl.nest, in_maps, out_map, dtype=np.float32,
                               flops_per_body=2 * 32 ** 3,
                               epilogue_flops=1e9)
    assert fused.compute_time > base.compute_time
    assert fused.flops == base.flops + 1e9
