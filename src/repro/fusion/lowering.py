"""Lower a ``TppGraph`` three ways (paper Fig. 1):

  * ``path="xla"``    — the reference: compose the ``core.tpp`` functions on
    full arrays and let XLA fuse them (the paper's "straightforward"
    framework path);
  * ``path="pallas"`` — ONE fused Pallas kernel: every contraction root runs
    under the same PARLOOPER ``loop_spec_string`` (letters ``a``=K reduction,
    ``b``=M, ``c``=N, exactly ``kernels.brgemm``) with one fp32 accumulator
    tile per root — a shared lhs operand is loaded once per (M, K) visit and
    feeds all its roots' MXU issues — the epilogue DAG is applied to the
    VMEM-resident accumulator tiles, and normalizing epilogues (layernorm /
    rmsnorm / softmax over N) use the row-panel statistics trick of
    ``kernels.fused_output``: the pre-norm row panel is staged in VMEM
    scratch, (sum, sum-of-squares) statistics accumulate per N tile, and the
    normalization equation is applied to the finished panel on the last N
    visit.  Multi-output graphs write each output value into a leading
    stacking axis → (R, M, N) (fused QKV);
  * the cost path lives in ``fusion.cost`` (perf-model + autotune hook).

``compile`` first runs ``simplify_graph`` (dropping identity / rate-0 dropout
nodes and now-unreferenced operands); operands the simplification removed are
still *accepted* at call time and ignored, so callers keep one call signature
per graph family.

Legality: besides the usual K-innermost requirement
(``validate_reduction_innermost``), a normalizing epilogue pins the N loop to
the nest's innermost band *under* every M level — a row's tiles must be
visited consecutively for its statistics to close before the panel is reused.
``validate_epilogue_band`` diagnoses schedules that violate this instead of
producing silently wrong kernels (the paper leaves such legality to the user).
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tpp
from repro.core.autotune import _freeze as _freeze_kw
from repro.obs import metrics as obs_metrics, trace as obs_trace
from repro.core.loops import LoopSpec, ThreadedLoop
from repro.core.pallas_lowering import (TensorMap, make_pallas_fn, plan_pallas,
                                        validate_reduction_innermost)
from repro.fusion.graph import (EPILOGUE_OPS, FusionLegalityError, TppGraph,
                                _MASK_FLOOR, _NEG_INF, simplify_graph)

__all__ = [
    "compile", "compile_for_backend", "validate_epilogue_band",
    "build_nest_inputs", "DEFAULT_SPEC",
    "fallback_blocklist", "clear_fallback_blocklist", "force_pallas_failure",
]

DEFAULT_SPEC = "bca"  # M, N outer; K (reduction) innermost — output-stationary


# ---------------------------------------------------------------------------
# Legality
# ---------------------------------------------------------------------------

def validate_epilogue_band(nest, graph: TppGraph, *, m_letter="b", n_letter="c"):
    """A normalizing epilogue reduces over N; its row panel closes only when
    all N tiles of a row are visited consecutively.  Reject schedules where
    any N level sits outside (above) an M level, where the N loop is
    parallelized (statistics accumulate sequentially), or where N is sharded
    over a mesh axis (the row statistics would be partial per shard)."""
    from repro.analysis import footprint

    footprint.enforce(
        footprint.check_epilogue_band(nest, graph, m_letter=m_letter,
                                      n_letter=n_letter),
        exc=FusionLegalityError,
    )


# ---------------------------------------------------------------------------
# Shared nest construction (also used by fusion.cost)
# ---------------------------------------------------------------------------

def build_nest_inputs(graph: TppGraph, m: int, k: int, n: int,
                      tiles: tuple[int, int, int],
                      block_steps: Optional[dict] = None, *,
                      rhs_widths: Optional[dict] = None,
                      chain_n2: Optional[int] = None):
    """LoopSpecs + TensorMaps for lowering ``graph`` at problem size
    (M, K, N) with base tiles (bm, bk, bn).  Operand order is
    ``[*contraction_operands, *epilogue_operands]`` (shared lhs operands
    mapped — and fetched — once); row vectors are fully VMEM-resident
    ``(1, n)`` blocks, (M, N) operands are tiled with the output — except
    operands consumed by the reducing node or a post-reduce node, which get
    full-row ``(bm, n)`` blocks (the close branch needs complete rows).
    Contraction operands with ``trans=True`` are mapped with their stored
    (transposed) layout — lhs (K, M), rhs (N, K) — and the kernel issues the
    MXU op with swapped contraction dims instead of materializing a
    transpose.  A multi-output graph's out map carries a leading unindexed
    stacking axis of extent R (array shape ``(R, M, N)``).

    ``rhs_widths`` maps rhs operand names to a *narrow* N width ``w < n``
    (per-root widths — GQA's K/V projections): the whole stored array is
    VMEM-resident every call (``(bk·steps, w)`` blocks, no ``c`` letter) and
    the kernel slices the live N tile out of it, skipping tiles past ``w``.
    ``chain_n2`` is the chained contraction's output width: crhs operands
    map as ``(bn·steps, n2)`` blocks walked by ``c``, and the (single)
    output of a chained graph maps full-width ``(bm, n2)`` rows."""
    bm, bk, bn = tiles
    if m % bm or k % bk or n % bn:
        raise FusionLegalityError(
            f"graph {graph.name!r}: problem ({m},{k},{n}) not divisible by "
            f"tiles ({bm},{bk},{bn}) — pick tiles dividing the problem "
            "shape (pick_tiles chooses divisors automatically)",
            code="TPP108")
    mb, kb, nb = m // bm, k // bk, n // bn
    block_steps = block_steps or {}
    rhs_widths = rhs_widths or {}
    if graph.chained_root() is not None and chain_n2 is None:
        chain_n2 = k   # attention default: the chain restores the lhs width
    loops = [
        LoopSpec(0, kb, 1, block_steps=tuple(block_steps.get("a", ())), name="K"),
        LoopSpec(0, mb, 1, block_steps=tuple(block_steps.get("b", ())), name="M"),
        LoopSpec(0, nb, 1, block_steps=tuple(block_steps.get("c", ())), name="N"),
    ]
    row_res = graph.row_resident_operands()
    in_maps = []
    for spec in graph.contraction_operands:
        if spec.kind == "lhs":
            in_maps.append(TensorMap(("a", "b"), (bk, bm), layout="flat")
                           if spec.trans
                           else TensorMap(("b", "a"), (bm, bk), layout="flat"))
        elif spec.kind == "crhs":
            # stored (N, N2), walked by the N loop, full chain width visible
            in_maps.append(TensorMap(("c", None), (bn, chain_n2),
                                     layout="flat"))
        elif spec.name in rhs_widths:
            # narrow rhs: whole stored array resident, no N-loop indexing
            w = rhs_widths[spec.name]
            in_maps.append(TensorMap((None, "a"), (w, bk), layout="flat")
                           if spec.trans
                           else TensorMap(("a", None), (bk, w), layout="flat"))
        else:
            in_maps.append(TensorMap(("c", "a"), (bn, bk), layout="flat")
                           if spec.trans
                           else TensorMap(("a", "c"), (bk, bn), layout="flat"))
    for spec in graph.epilogue_operands:
        if spec.kind in ("tile", "mask"):
            in_maps.append(
                TensorMap(("b", None), (bm, n), layout="flat")
                if spec.name in row_res
                else TensorMap(("b", "c"), (bm, bn), layout="flat"))
        elif spec.kind == "scalar":   # traced scalar (PRNG seed) — one elem
            in_maps.append(TensorMap((None, None), (1, 1), layout="flat"))
        else:  # rowvec — whole vector visible every call (norms need full N)
            in_maps.append(TensorMap((None, None), (1, n), layout="flat"))
    n_out = len(graph.outputs)
    if graph.chained_root() is not None:
        # single output (validated), full chain width per row block
        out_map = TensorMap(("b", None), (bm, chain_n2), layout="flat")
    elif graph.reducing_node() is not None:
        out_map = (TensorMap((None, "b", None), (n_out, bm, n), layout="flat")
                   if n_out > 1
                   else TensorMap(("b", None), (bm, n), layout="flat"))
    elif n_out > 1:
        out_map = TensorMap((None, "b", "c"), (n_out, bm, bn), layout="flat")
    else:
        out_map = TensorMap(("b", "c"), (bm, bn), layout="flat")
    return loops, in_maps, out_map


def _pack_operands(graph: TppGraph, operands: dict, ignore=frozenset()):
    """Canonically order ([*contraction-operands, *epilogue-operands]) and
    reshape call-time operands: rowvecs (n,) → (1, n).  Canonical order is
    independent of the graph's declaration order — the Pallas lowering's
    TensorMaps are built in the same order.  Names in ``ignore`` (operands a
    simplification pass removed from the graph) are accepted and dropped."""
    packed = []
    for spec in graph.contraction_operands + graph.epilogue_operands:
        if spec.name not in operands:
            raise TypeError(
                f"graph {graph.name!r}: missing operand {spec.name!r}; "
                f"expected {graph.operand_names}")
        v = operands[spec.name]
        if spec.kind == "rowvec":
            v = v.reshape(1, -1)
        elif spec.kind == "scalar":
            v = jnp.asarray(v).reshape(1, 1)
        packed.append(v)
    extra = set(operands) - set(graph.operand_names) - set(ignore)
    if extra:
        raise TypeError(f"graph {graph.name!r}: unexpected operands {sorted(extra)}")
    return packed


# ---------------------------------------------------------------------------
# Path 1: XLA reference — compose core.tpp functions, let XLA fuse
# ---------------------------------------------------------------------------

def _compile_xla(graph: TppGraph, *, out_dtype=None, ignore=frozenset()):
    def fn(**operands):
        _pack_operands(graph, operands, ignore)  # validates the operand set
        base = graph.base_roots
        x = operands[base[0].lhs]
        env = {}
        for root in base:
            a, b = operands[root.lhs], operands[root.rhs]
            if graph.operand(root.lhs).trans:
                a = a.T
            if graph.operand(root.rhs).trans:
                b = b.T
            env[root.name] = tpp.gemm(a, b, beta=0.0, out_dtype=jnp.float32)
        if len(graph.roots) == 1:
            env["acc"] = env[graph.roots[0].name]

        def value(ref):
            if ref in env:
                return env[ref]
            spec = graph.operand(ref)
            v = operands[ref]
            return (v if spec.kind in ("mask", "scalar")
                    else v.astype(jnp.float32))

        for nd in graph.nodes:
            op = EPILOGUE_OPS[nd.op]
            # wants_offsets ops see the full (M, N) array here — the global
            # coordinates ARE the local ones, so the (0, 0) default applies
            env[nd.name] = op.apply(*(value(r) for r in nd.inputs),
                                    **nd.attr_dict())
        # a chained root consumes the reduced panel, so it evaluates AFTER
        # the epilogue DAG: the composed reference is softmax-then-matmul,
        # mathematically identical to the streamed online recurrence
        for root in graph.roots:
            if root.chained:
                env[root.name] = tpp.gemm(env[root.lhs],
                                          operands[root.rhs].astype(
                                              jnp.float32),
                                          beta=0.0, out_dtype=jnp.float32)
        odt = out_dtype or x.dtype
        if len(graph.outputs) > 1:
            outs = [env[o] for o in graph.outputs]
            # per-root N widths (GQA): narrow roots zero-pad to the stack
            # width, matching the Pallas kernel's never-touched acc columns
            wmax = max(o.shape[-1] for o in outs)
            outs = [o if o.shape[-1] == wmax
                    else jnp.pad(o, ((0, 0), (0, wmax - o.shape[-1])))
                    for o in outs]
            return jnp.stack(outs).astype(odt)
        return env[graph.outputs[0]].astype(odt)

    return fn


# ---------------------------------------------------------------------------
# Path 2: one fused Pallas kernel
# ---------------------------------------------------------------------------

# Reducing ops whose close-branch formula recovers the row statistics from
# the (sum, sum-of-squares) strip accumulated tile-by-tile over the staged
# stats panel, instead of re-reducing the finished panel.  ``vals`` are the
# op's full-row value inputs, ``params`` its full-row (1, n) operand inputs.

def _ln_close(vals, params, stats, n, attrs):
    (z,) = vals
    gamma, beta = params
    mu = stats[:, 0:1] / n
    var = jnp.maximum(stats[:, 1:2] / n - mu * mu, 0.0)
    y = (z - mu) * jax.lax.rsqrt(var + attrs.get("eps", 1e-5))
    return y * gamma + beta


def _rms_close(vals, params, stats, n, attrs):
    (z,) = vals
    ms = stats[:, 1:2] / n
    return z * jax.lax.rsqrt(ms + attrs.get("eps", 1e-6)) * params[0]


def _ln_grad_close(vals, params, stats, n, attrs):
    dv, z = vals
    mu = stats[:, 0:1] / n
    var = jnp.maximum(stats[:, 1:2] / n - mu * mu, 0.0)
    rstd = jax.lax.rsqrt(var + attrs.get("eps", 1e-5))
    xhat = (z - mu) * rstd
    g = dv * params[0]
    return rstd * (g - jnp.mean(g, axis=1, keepdims=True)
                   - xhat * jnp.mean(g * xhat, axis=1, keepdims=True))


def _ln_gamma_close(vals, params, stats, n, attrs):
    dv, z = vals
    mu = stats[:, 0:1] / n
    var = jnp.maximum(stats[:, 1:2] / n - mu * mu, 0.0)
    return dv * (z - mu) * jax.lax.rsqrt(var + attrs.get("eps", 1e-5))


def _rms_grad_close(vals, params, stats, n, attrs):
    dv, z = vals
    ms = stats[:, 1:2] / n
    r = jax.lax.rsqrt(ms + attrs.get("eps", 1e-6))
    g = dv * params[0]
    return r * g - (r ** 3) * z * (jnp.sum(g * z, axis=1, keepdims=True) / n)


def _rms_gamma_close(vals, params, stats, n, attrs):
    dv, z = vals
    ms = stats[:, 1:2] / n
    return dv * z * jax.lax.rsqrt(ms + attrs.get("eps", 1e-6))


_STATS_CLOSE = {
    "layernorm": _ln_close,
    "rmsnorm": _rms_close,
    "layernorm_grad": _ln_grad_close,
    "layernorm_gamma_grad": _ln_gamma_close,
    "rmsnorm_grad": _rms_grad_close,
    "rmsnorm_gamma_grad": _rms_gamma_close,
}


def contraction_operand_values(graph: TppGraph) -> frozenset[str]:
    """Contraction (lhs/rhs) operands referenced as epilogue *values*.  The
    XLA path supports them (full arrays); the Pallas kernel cannot — at
    epilogue time the VMEM-resident lhs/rhs tile is the last (K-indexed)
    fetch, not an (M, N)-shaped value."""
    con = {o.name for o in graph.operands if o.kind in ("lhs", "rhs")}
    return frozenset(r for nd in graph.nodes for r in nd.inputs if r in con)


def _compile_pallas(graph: TppGraph, *, spec_string=DEFAULT_SPEC, tiles=None,
                    block_steps=None, out_dtype=None, interpret=False,
                    mesh=None, vmem_limit_bytes=None, hw_prng=False,
                    ignore=frozenset()):
    bad = contraction_operand_values(graph)
    if bad:
        raise FusionLegalityError(
            f"graph {graph.name!r}: contraction operand(s) {sorted(bad)} are "
            "referenced as epilogue values — the fused Pallas kernel only "
            "sees their K-indexed tiles at epilogue time; use the XLA path "
            "for this graph", code="TPP207")
    reducing = graph.reducing_node()
    red_idx = graph.nodes.index(reducing) if reducing is not None else None
    pre_nodes = graph.nodes if reducing is None else graph.nodes[:red_idx]
    post_nodes = graph.post_reduce_nodes()
    chain = graph.chained_root()
    base_roots = graph.base_roots
    # a chained graph stages NOTHING: the reduced value streams straight
    # into the chain accumulator under the (running max, running sum) strip
    staged = () if chain is not None else graph.staged_values()
    row_res = graph.row_resident_operands()
    con_specs = graph.contraction_operands
    ep_specs = graph.epilogue_operands
    roots = graph.roots
    outputs = graph.outputs
    # position of each contraction operand in the packed/ref order
    con_pos = {s.name: i for i, s in enumerate(con_specs)}
    con_trans = {s.name: s.trans for s in con_specs}
    red_op = EPILOGUE_OPS[reducing.op] if reducing is not None else None
    # the stats strip accumulates (sum, sum-sq) of the op's declared stats
    # input tile-by-tile — only possible when that input is a staged panel
    # (a computed value); ops without a stats formula run a full-row apply
    use_stats = (
        reducing is not None and red_op.stats_input is not None
        and reducing.op in _STATS_CLOSE
        and reducing.inputs[red_op.stats_input] in staged)
    stats_name = (reducing.inputs[red_op.stats_input] if use_stats else None)
    # the chained recurrence streams the reducer's stats input (the masked
    # score tile): max/sum update + rescale, never a materialized panel
    chain_in = (reducing.inputs[red_op.stats_input]
                if chain is not None else None)
    # counter-PRNG ops key their draw on global element coordinates; the
    # hardware generator (opt-in, real TPU only — interpret mode has no HW
    # PRNG) trades that schedule invariance for throughput
    has_offset_ops = any(EPILOGUE_OPS[nd.op].wants_offsets
                         for nd in graph.nodes)
    use_hw_bits = bool(hw_prng) and not interpret
    # root accumulators consumed by the epilogue DAG: those roots must carry
    # the full N width — only output-only roots may be narrow (their pad
    # columns are never read, just stacked as zeros)
    consumed_roots = {graph.resolve_acc(ref)
                      for nd in graph.nodes for ref in nd.inputs}
    plan_cache: dict = {}  # (operand shapes/dtypes) -> pallas call

    def build_call(m, k, n, x_dtype, odt, rhs_widths, chain_n2):
        # every call here is one planned fused nest for a NEW operand shape —
        # the recompile point the fusion.lowerings counter tracks
        obs_metrics.default_registry().counter("fusion.lowerings").inc()
        with obs_trace.get_tracer().span(
                "fusion.lower", cat="fusion", graph=graph.name,
                m=m, k=k, n=n, spec=spec_string):
            return _build_call(m, k, n, x_dtype, odt, rhs_widths, chain_n2)

    def _build_call(m, k, n, x_dtype, odt, rhs_widths, chain_n2):
        import math

        from repro.kernels.brgemm import pick_tiles
        bm, bk, bn = tiles or pick_tiles(m, k, n, x_dtype)
        if rhs_widths and tiles is None:
            # shrink the N tile so every narrow width is a whole number of
            # tiles (gcd still divides n); explicitly passed tiles are the
            # caller's contract and stay untouched — the check below rejects
            bn = math.gcd(bn, *rhs_widths.values())
        loops, in_maps, out_map = build_nest_inputs(
            graph, m, k, n, (bm, bk, bn), block_steps,
            rhs_widths=rhs_widths, chain_n2=chain_n2)
        tl = ThreadedLoop(loops, spec_string, reduction_letters=("a",))
        validate_reduction_innermost(tl.nest, ("b", "c"), ("a",))
        validate_epilogue_band(tl.nest, graph)
        if has_offset_ops:
            from repro.analysis import footprint
            footprint.enforce(footprint.check_prng_mesh(tl.nest, graph),
                              exc=FusionLegalityError)
        plan = plan_pallas(tl.nest, in_maps, out_map, reduction_letters=("a",))

        kb = k // bk
        nb = n // bn
        k_step = tl.nest.innermost_step("a")
        c_step = tl.nest.innermost_step("c")
        acc_m = tl.nest.innermost_step("b") * bm
        acc_n = c_step * bn
        for nm, w in rhs_widths.items():
            if w % acc_n:
                raise FusionLegalityError(
                    f"graph {graph.name!r}: narrow rhs operand {nm!r} width "
                    f"{w} is not a whole number of N blocks (block {acc_n}) "
                    "— pass tiles/block_steps whose N block divides every "
                    "per-root width", code="TPP108")
        n_con = len(con_specs)
        n_ep = len(ep_specs)
        n_out = len(outputs)
        # width of each base root's accumulator band (n unless its rhs is
        # narrow); accumulation for tiles past the band is skipped, leaving
        # the zero-initialized columns in place — the stacked output is
        # therefore zero-padded, exactly like the XLA path
        root_w = {r.name: rhs_widths.get(r.rhs, n) for r in base_roots}

        def body(ind, *refs):
            con_refs = refs[:n_con]
            ep_refs = {s.name: r
                       for s, r in zip(ep_specs, refs[n_con:n_con + n_ep])}
            o_ref = refs[n_con + n_ep]
            scratch = refs[n_con + n_ep + 1:]
            acc_refs = {r.name: scratch[i] for i, r in enumerate(base_roots)}
            n_acc = len(base_roots)
            if chain is not None:
                chain_ref, cstats_ref = scratch[n_acc], scratch[n_acc + 1]
                panel_refs, stats_ref = {}, None
            else:
                chain_ref = cstats_ref = None
                panel_refs = {nm: scratch[n_acc + i]
                              for i, nm in enumerate(staged)}
                stats_ref = (scratch[n_acc + len(staged)]
                             if use_stats else None)
            ik = ind["a"]
            jc = ind["c"]
            ib = ind["b"]

            def node_kwargs(nd, op, col0):
                """Static attrs + (for PRNG ops) the tile's global element
                offsets: rows start at ib*bm, cols at ``col0`` (the current
                N tile for pre-reduce nodes, 0 for full-row panels)."""
                kw = nd.attr_dict()
                if op.wants_offsets:
                    kw["_offsets"] = (ib * bm, col0)
                    if use_hw_bits:
                        kw["_impl"] = "hw"
                return kw

            if use_stats:
                @pl.when(jnp.logical_and(jc == 0, ik == 0))
                def _():
                    stats_ref[...] = jnp.zeros_like(stats_ref)

            if chain is not None:
                # chain accumulator + (running max, running sum) strip live
                # across EVERY N visit of a row — reset only at row start
                @pl.when(jnp.logical_and(jc == 0, ik == 0))
                def _():
                    chain_ref[...] = tpp.zero(chain_ref.shape, chain_ref.dtype)
                    cstats_ref[:, 0] = jnp.full((acc_m,), _NEG_INF,
                                                jnp.float32)
                    cstats_ref[:, 1] = jnp.zeros((acc_m,), jnp.float32)

            @pl.when(ik == 0)
            def _():
                for acc_ref in acc_refs.values():
                    acc_ref[...] = tpp.zero(acc_ref.shape, acc_ref.dtype)

            # one MXU issue per base root; a shared lhs tile is read from
            # its (single) VMEM ref once per root, fetched from HBM once.  A
            # trans operand's tile arrives in stored (transposed) layout —
            # the dot_general contracts over the matching dim instead of
            # materializing a transpose.  A narrow rhs (per-root N width,
            # GQA) is wholly resident: slice the live N tile out of it and
            # skip tiles past the width — those acc columns stay zero.
            for root in base_roots:
                lc = 0 if con_trans[root.lhs] else 1
                rc = 1 if con_trans[root.rhs] else 0
                a_ref = con_refs[con_pos[root.lhs]]
                b_ref = con_refs[con_pos[root.rhs]]
                if root_w[root.name] == n:
                    acc_refs[root.name][...] += jax.lax.dot_general(
                        a_ref[...], b_ref[...],
                        dimension_numbers=(((lc,), (rc,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                else:
                    def _narrow_acc(root=root, a_ref=a_ref, b_ref=b_ref,
                                    lc=lc, rc=rc):
                        tile = (b_ref[pl.ds(jc * bn, acc_n), :]
                                if con_trans[root.rhs]
                                else b_ref[:, pl.ds(jc * bn, acc_n)])
                        acc_refs[root.name][...] += jax.lax.dot_general(
                            a_ref[...], tile,
                            dimension_numbers=(((lc,), (rc,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    pl.when(jc * bn + acc_n <= root_w[root.name])(_narrow_acc)

            # last K visit: run the epilogue DAG on the VMEM-resident tiles
            @pl.when(ik == kb - k_step)
            def _():
                env = {r.name: acc_refs[r.name][...] for r in base_roots}
                if len(roots) == 1:
                    env["acc"] = env[roots[0].name]

                def value(ref, full_row=False):
                    if ref in env:
                        return env[ref]
                    spec = graph.operand(ref)
                    r = ep_refs[ref]
                    if spec.kind == "rowvec":
                        v = r[...] if full_row else r[:, pl.ds(jc * bn, acc_n)]
                        return v.astype(jnp.float32)
                    if spec.name in row_res and not full_row:
                        # full-row block; pre-reduce consumers slice their
                        # current N tile out of it
                        v = r[:, pl.ds(jc * bn, acc_n)]
                    else:
                        v = r[...]
                    return (v if spec.kind in ("mask", "scalar")
                            else v.astype(jnp.float32))

                for nd in pre_nodes:
                    op = EPILOGUE_OPS[nd.op]
                    env[nd.name] = op.apply(
                        *(value(r) for r in nd.inputs),
                        **node_kwargs(nd, op, jc * bn))

                if reducing is None:
                    if n_out > 1:
                        o_ref[...] = jnp.stack(
                            [env[o] for o in outputs]).astype(o_ref.dtype)
                    else:
                        o_ref[...] = env[outputs[0]].astype(o_ref.dtype)
                    return

                if chain is not None:
                    # streaming online-softmax recurrence (the statistics
                    # strip generalized from (sum, sum-sq) to (running max,
                    # running sum)): when a new N tile raises a row's max,
                    # both the running sum and the chain accumulator are
                    # rescaled by exp(m_prev - m_new) — so at the final N
                    # visit chain/l IS softmax(z) @ V without the (M, N)
                    # panel ever existing.  Scores at/below _MASK_FLOOR are
                    # masked-out fills: their exp contribution is pinned to
                    # zero (a fully-masked tile must not contribute exp(0)
                    # while the running max is still _NEG_INF).
                    zt = env[chain_in]
                    m_prev = cstats_ref[:, 0:1]
                    l_prev = cstats_ref[:, 1:2]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(zt, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.where(zt > _MASK_FLOOR,
                                  jnp.exp(zt - m_new), 0.0)
                    cstats_ref[:, 0] = m_new[:, 0]
                    cstats_ref[:, 1] = (l_prev * alpha
                                        + jnp.sum(p, axis=1,
                                                  keepdims=True))[:, 0]
                    v_tile = con_refs[con_pos[chain.rhs]][...].astype(
                        jnp.float32)
                    chain_ref[...] = chain_ref[...] * alpha + \
                        jax.lax.dot_general(
                            p, v_tile,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

                    @pl.when(jc == nb - c_step)
                    def _():
                        # close: normalize by the running sum.  A fully
                        # masked row has l == 0 → output 0 (the reference
                        # kernels' convention), never a division by zero.
                        l = jnp.maximum(cstats_ref[:, 1:2], 1e-30)
                        o_ref[...] = (chain_ref[...] / l).astype(o_ref.dtype)
                    return

                # row-panel statistics trick (kernels.fused_output,
                # generalized): stage each computed value the reducing op
                # consumes, close the (sum, sum-sq) strip over its stats
                # input, and run the reduction — plus any post-reduce
                # pointwise nodes — on the finished panels at the last N
                # visit
                for nm in staged:
                    panel_refs[nm][:, pl.ds(jc * bn, acc_n)] = env[nm]
                if use_stats:
                    zt = env[stats_name]
                    stats_ref[:, 0] += jnp.sum(zt, axis=1)
                    stats_ref[:, 1] += jnp.sum(zt * zt, axis=1)

                @pl.when(jc == nb - c_step)
                def _():
                    attrs = reducing.attr_dict()
                    fullenv = {nm: panel_refs[nm][...] for nm in staged}

                    def fval(ref):
                        if ref in fullenv:
                            return fullenv[ref]
                        return value(ref, full_row=True)

                    vals = [fval(r)
                            for r in reducing.inputs[:red_op.value_arity]]
                    params = [fval(r)
                              for r in reducing.inputs[red_op.value_arity:]]
                    if use_stats:
                        y = _STATS_CLOSE[reducing.op](
                            vals, params, stats_ref[...], n, attrs)
                    else:  # softmax & any panel-wide reducer: full-row apply
                        y = red_op.apply(*vals, *params, **attrs)
                    fullenv[reducing.name] = y

                    for nd in post_nodes:
                        op = EPILOGUE_OPS[nd.op]
                        fullenv[nd.name] = op.apply(
                            *(fval(r) for r in nd.inputs),
                            **node_kwargs(nd, op, 0))

                    if n_out > 1:
                        o_ref[...] = jnp.stack(
                            [fullenv[o] for o in outputs]).astype(o_ref.dtype)
                    else:
                        o_ref[...] = fullenv[outputs[0]].astype(o_ref.dtype)

        scratch_shapes = [pltpu.VMEM((acc_m, acc_n), jnp.float32)
                          for _ in base_roots]
        if chain is not None:
            scratch_shapes += [
                pltpu.VMEM((acc_m, chain_n2), jnp.float32),   # chain acc
                pltpu.VMEM((acc_m, 2), jnp.float32),  # (run max, run sum)
            ]
        elif reducing is not None:
            scratch_shapes += [pltpu.VMEM((acc_m, n), jnp.float32)
                               for _ in staged]       # staged row panels
            if use_stats:
                scratch_shapes.append(
                    pltpu.VMEM((acc_m, 2), jnp.float32))  # (sum, sum-sq)

        db = jnp.dtype(x_dtype).itemsize
        ep_elems = sum(
            (m * n if s.kind in ("tile", "mask")
             else (1 if s.kind == "scalar" else n)) for s in ep_specs)
        con_elems = sum(
            (m * k if s.kind == "lhs"
             else n * chain_n2 if s.kind == "crhs"
             else k * rhs_widths.get(s.name, n)) for s in con_specs)
        if chain is not None:
            out_shape = (m, chain_n2)
            out_elems = m * chain_n2
        else:
            out_shape = (n_out, m, n) if n_out > 1 else (m, n)
            out_elems = n_out * m * n
        return make_pallas_fn(
            plan,
            body,
            jax.ShapeDtypeStruct(out_shape, odt),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            mesh=mesh,
            vmem_limit_bytes=vmem_limit_bytes,
            cost_estimate=pl.CostEstimate(
                flops=2 * m * n * k * len(base_roots)
                + (2 * m * n * chain_n2 if chain is not None else 0)
                + int(graph.epilogue_flops_per_elem() * m * n),
                bytes_accessed=(con_elems + ep_elems) * db
                + out_elems * jnp.dtype(odt).itemsize,
                transcendentals=0,
            ),
        )

    def fn(**operands):
        packed = _pack_operands(graph, operands, ignore)
        x = packed[0]   # contraction_operands lead with roots[0].lhs
        if con_specs[0].trans:
            k, m = x.shape
        else:
            m, k = x.shape
        for spec, v in zip(con_specs, packed):
            if spec.kind == "lhs":
                want = (k, m) if spec.trans else (m, k)
                if v.shape != want:
                    raise FusionLegalityError(
                        f"graph {graph.name!r}: lhs operand {spec.name!r} "
                        f"has shape {v.shape}, expected {want} — multi-root "
                        "graphs share one (M, K, N) problem shape")
        # per-root N widths: every rhs must share K; the nest's N is the
        # WIDEST rhs, narrower ones (GQA K/V) become sliced-resident maps
        widths = {}
        for spec, v in zip(con_specs, packed):
            if spec.kind != "rhs":
                continue
            kk, w = ((v.shape[1], v.shape[0]) if spec.trans else v.shape)
            if kk != k:
                raise FusionLegalityError(
                    f"graph {graph.name!r}: rhs operand {spec.name!r} has "
                    f"shape {v.shape}, expected K = {k} on its contraction "
                    "dim — all roots share the (M, K) problem")
            widths[spec.name] = w
        n = max(widths.values())
        rhs_widths = {nm: w for nm, w in widths.items() if w < n}
        if rhs_widths:
            bad = sorted(r.name for r in base_roots
                         if r.rhs in rhs_widths and r.name in consumed_roots)
            if bad:
                raise FusionLegalityError(
                    f"graph {graph.name!r}: rhs widths differ ({widths}) but "
                    f"root(s) {bad} feed epilogue nodes — per-root N widths "
                    "apply only to output-only roots (stacked, zero-padded); "
                    "epilogue-combined roots share one (M, K, N) problem "
                    "shape")
        chain_n2 = None
        if chain is not None:
            cv = packed[con_pos[chain.rhs]]
            if cv.ndim != 2 or cv.shape[0] != n:
                raise FusionLegalityError(
                    f"graph {graph.name!r}: crhs operand {chain.rhs!r} has "
                    f"shape {getattr(cv, 'shape', None)}, expected (N, N2) "
                    f"= ({n}, *) — the chain contracts over the base "
                    "roots' N axis", code="TPP213")
            chain_n2 = cv.shape[1]
        odt = out_dtype or x.dtype
        key = tuple((v.shape, jnp.dtype(v.dtype).name) for v in packed)
        call = plan_cache.get(key)
        if call is None:
            call = build_call(m, k, n, x.dtype, odt, rhs_widths, chain_n2)
            plan_cache[key] = call
        return call(*packed)

    return fn


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def compile(graph: TppGraph, *, path: str = "pallas", simplify: bool = True,
            **kw):
    """Lower ``graph`` to a callable ``fn(**operands) -> (M, N) array``
    (``(R, M, N)`` for an R-output graph).

    The graph is first run through :func:`simplify_graph` (identity / rate-0
    dropout elimination + dead-operand removal); operands the simplification
    dropped remain accepted — and ignored — at call time.  ``path="pallas"``
    (default) emits one fused Pallas kernel; ``path="xla"`` emits the
    composed-TPP reference.  Keyword options for the Pallas path:
    ``spec_string``, ``tiles``, ``block_steps``, ``out_dtype``, ``interpret``,
    ``mesh``, ``vmem_limit_bytes``, ``hw_prng`` (draw ``dropout_rng`` bits
    from the TPU hardware generator — faster on real TPUs but NOT
    schedule-invariant or reference-bit-identical; see ``fusion.rng``); the
    XLA path takes ``out_dtype`` only.
    """
    lowered = simplify_graph(graph) if simplify else graph
    # Two live same-kind PRNG draws sharing a salt would emit identical bits
    # at both sites — a silent correctness bug; refuse to compile (TPP203).
    from repro.fusion import rng
    rng.assert_unique_salts(lowered)
    ignore = frozenset(graph.operand_names) - frozenset(lowered.operand_names)
    if path == "xla":
        allowed = {"out_dtype"}
        bad = set(kw) - allowed
        if bad:
            raise TypeError(f"xla path does not accept {sorted(bad)}")
        return _compile_xla(lowered, ignore=ignore, **kw)
    if path == "pallas":
        return _compile_pallas(lowered, ignore=ignore, **kw)
    raise ValueError(f"unknown lowering path {path!r}; use 'pallas' or 'xla'")


_COMPILE_CACHE: dict = {}

# Graceful degradation: graphs whose fused Pallas lowering failed, now
# permanently routed through the composed-TPP XLA reference (the paper's
# "every primitive has a reference semantic" payoff).  Keyed by the graph
# itself; ``fallback_blocklist()`` exposes a name→reason view.
_FALLBACK_BLOCKLIST: dict = {}
_FORCED_FAILURES: set[str] = set()   # graph names (fault injection / tests)
_LOG = logging.getLogger("repro.fusion")


class ForcedPallasFailure(RuntimeError):
    """Raised in place of running a fused kernel under
    :func:`force_pallas_failure` — exercises the XLA fallback path."""


def _fallback_enabled() -> bool:
    # opt-in (REPRO_FUSION_FALLBACK=1): by default a lowering failure is
    # fatal, so a run that asked for fused kernels never reports success
    # on the reference path without them
    return os.environ.get("REPRO_FUSION_FALLBACK", "0") == "1"


def fallback_blocklist() -> dict[str, str]:
    """{graph name: failure reason} for every graph currently degraded to
    the XLA reference."""
    return {g.name: reason for g, reason in _FALLBACK_BLOCKLIST.items()}


def clear_fallback_blocklist() -> None:
    """Forget recorded lowering failures (e.g. after an env/backend change
    that may have fixed them); blocklisted graphs will retry Pallas on
    their next fresh compile."""
    _FALLBACK_BLOCKLIST.clear()


@contextlib.contextmanager
def force_pallas_failure(*names: str):
    """Fault injection: within the context, calling the fused Pallas
    lowering of the named graphs raises, driving ``compile_for_backend``'s
    XLA fallback.  On exit the forcing — and any blocklist entries it
    caused — are removed, so a chaos test leaves the process clean."""
    _FORCED_FAILURES.update(names)
    try:
        yield
    finally:
        _FORCED_FAILURES.difference_update(names)
        for g in [g for g in _FALLBACK_BLOCKLIST if g.name in names]:
            del _FALLBACK_BLOCKLIST[g]


def _note_fallback(graph: TppGraph, exc: BaseException) -> None:
    if graph not in _FALLBACK_BLOCKLIST:
        reason = f"{type(exc).__name__}: {exc}"
        _FALLBACK_BLOCKLIST[graph] = reason
        obs_metrics.default_registry().counter("fusion.fallbacks").inc()
        obs_trace.get_tracer().event("fusion.fallback", cat="fusion",
                                     graph=graph.name, reason=reason)
        _LOG.warning(
            "fused Pallas lowering of graph %r failed (%s); falling back to "
            "the composed-TPP XLA reference for this graph "
            "(REPRO_FUSION_FALLBACK=1)", graph.name, reason)


def _guarded_pallas(graph: TppGraph, backend: str, kw: dict):
    """Compile the fused Pallas path with an opt-in call-time XLA fallback
    (``REPRO_FUSION_FALLBACK=1``).  Pallas plan/lowering errors surface
    either at compile() time (epilogue-band legality) or at first call per
    shape (tile divisibility, Mosaic); with the fallback on, both are
    caught, logged once, blocklisted, and rerouted to the XLA reference.
    Without it they propagate.  ``TypeError`` (caller passed wrong
    operands) is always fatal."""
    xla_kw = {k: v for k, v in kw.items() if k == "out_dtype"}
    state: dict = {"xla_fn": None}

    def xla_fn():
        if state["xla_fn"] is None:
            state["xla_fn"] = compile(graph, path="xla", **xla_kw)
        return state["xla_fn"]

    try:
        pallas_fn = compile(graph, path="pallas",
                            interpret=(backend == "pallas_interpret"), **kw)
    except Exception as exc:
        if not _fallback_enabled():
            raise
        _note_fallback(graph, exc)
        pallas_fn = None

    def guarded(**operands):
        if pallas_fn is None or graph in _FALLBACK_BLOCKLIST:
            return xla_fn()(**operands)
        try:
            if graph.name in _FORCED_FAILURES:
                raise ForcedPallasFailure(
                    f"forced Pallas failure for graph {graph.name!r}")
            return pallas_fn(**operands)
        except TypeError:
            raise               # operand-signature error, not a lowering bug
        except Exception as exc:
            if not _fallback_enabled():
                raise
            _note_fallback(graph, exc)
            return xla_fn()(**operands)

    return guarded


def compile_for_backend(graph: TppGraph, backend: Optional[str] = None, **kw):
    """Pick the lowering path from the active ``kernels.ops`` backend — the
    hook ``models.blocks`` uses behind the ``use_fusion`` config flag.

    Compiled callables are memoized on ``(graph, backend, kwargs)`` — the
    library ``fused_*_apply`` helpers call this per layer invocation, and
    rebuilding the closure (plus re-planning the nest inside it) per eager
    call is pure waste.  The returned callable itself caches one pallas plan
    per distinct operand-shape/dtype tuple.

    Lowering failures raise, as in :func:`compile`.  With
    ``REPRO_FUSION_FALLBACK=1`` the pallas-backend callables returned here
    degrade instead: a graph whose fused lowering fails is logged once,
    blocklisted, and routed through the composed-TPP XLA reference, so
    ``use_fusion=True`` models survive a backend that cannot compile a
    shape (``fallback_blocklist()`` and the ``fusion.fallbacks`` counter
    say which)."""
    from repro.kernels import ops
    backend = backend or ops.current_backend()
    if backend == "xla":
        kw.pop("tiles", None)
        kw.pop("spec_string", None)
        kw.pop("block_steps", None)
        kw.pop("hw_prng", None)
    reg = obs_metrics.default_registry()
    try:
        key = (graph, backend,
               tuple(sorted((k, _freeze_kw(v)) for k, v in kw.items())))
        hit = _COMPILE_CACHE.get(key)
    except TypeError:   # unhashable kwarg (e.g. a live mesh object)
        key, hit = None, None
    if hit is not None:
        reg.counter("fusion.compile_cache.hits").inc()
        return hit
    reg.counter("fusion.compile_cache.misses").inc()
    with obs_trace.get_tracer().span("fusion.compile", cat="fusion",
                                     graph=graph.name, backend=backend):
        if backend == "xla":
            fn = compile(graph, path="xla", **kw)
        else:
            fn = _guarded_pallas(graph, backend, kw)
    if key is not None:
        _COMPILE_CACHE[key] = fn
    return fn
