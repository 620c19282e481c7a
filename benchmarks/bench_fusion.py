"""Fused TPP-chain vs unfused per-op vs hand-written kernel (paper §IV-A).

Three comparisons on the Bert-Output layer shape (Listing 6):

  * **wall (XLA)** — the fusion compiler's reference path (one jitted
    composed-TPP function) vs the honest unfused chain (one jitted function
    *per op*, forcing an HBM round-trip between operators, the op-by-op
    runtime the paper fuses away);
  * **model (Pallas plan)** — ``fusion.graph_cost`` of the fused nest vs
    ``fusion.estimate_unfused`` with the same schedule-aware GEMM pricing:
    predicted time and HBM bytes on the TPU target;
  * **parity** — the fused Pallas kernel (interpret mode) against the
    hand-written ``kernels.fused_output`` oracle (``--smoke`` only; interpret
    mode is too slow for timing).

A fourth comparison covers training-mode dropout: the legacy pre-generated
keep-mask graph (an extra (M, N) bool operand streamed through the nest)
against the in-kernel counter-PRNG graph (``dropout_rng`` — a scalar seed,
zero mask traffic).  The wall/model/traffic deltas land in
``BENCH_fusion_dropout.json``.

A fifth section runs the observability profiler (``repro.obs.profiler``)
over the fused library graphs: warmup+median wall time beside the perf
model's prediction per graph, with relative drift flags and the
process-global fusion/tune counters, written to
``BENCH_fusion_profile.json`` (see docs/observability.md).

Row format matches the other benchmarks: ``name,usec,extras``.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import fusion
from repro.core import perf_model
from repro.fusion import rng as frng
from repro.fusion.library import OUTPUT_DROPOUT_SALT
from repro.kernels.brgemm import pick_tiles
from repro.launch.compile_cache import enable_compile_cache

DROPOUT_JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_fusion_dropout.json")
PROFILE_JSON_PATH = os.path.join(os.path.dirname(DROPOUT_JSON_PATH),
                                 "BENCH_fusion_profile.json")


def _bench(fn, iters=10):
    jax.block_until_ready(fn())  # warm the jit cache
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def _unfused_chain_fns(graph):
    """One jitted function per operator — each call round-trips through HBM."""
    gemm = jax.jit(lambda x, w: jnp.dot(
        x, w, preferred_element_type=jnp.float32))
    steps = []
    for nd in graph.nodes:
        op = fusion.EPILOGUE_OPS[nd.op]
        attrs = nd.attr_dict()
        extra = nd.inputs[op.value_arity:]
        steps.append((jax.jit(lambda v, *p, _op=op, _at=attrs:
                              _op.apply(v, *p, **_at)), extra))
    return gemm, steps


def run(smoke: bool = False):
    rows = []
    rng = np.random.default_rng(0)
    # Bert-large Output layer: d_ff=4096 → d=1024, tokens = minibatch·seq
    shapes = [(256, 512, 512)] if smoke else [(4096, 4096, 1024),
                                              (8192, 1024, 1024)]
    dropout = 0.1
    for (m, k, n) in shapes:
        graph = fusion.fused_output_graph(dropout)   # in-kernel PRNG dropout
        dt = np.float32
        ops = {
            "x": jnp.asarray(rng.normal(size=(m, k)).astype(dt)),
            "w": jnp.asarray(rng.normal(size=(k, n)).astype(dt)),
            "bias": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
            "seed": jnp.asarray(17, jnp.uint32),
            "residual": jnp.asarray(rng.normal(size=(m, n)).astype(dt)),
            "gamma": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
            "beta": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
        }

        # ---- wall: fused (one jit) vs unfused (jit per op) ---------------
        fused_fn = jax.jit(fusion.compile(graph, path="xla"))
        t_fused = _bench(lambda: fused_fn(**ops), iters=5 if smoke else 10)

        gemm, steps = _unfused_chain_fns(graph)

        def unfused():
            v = gemm(ops["x"], ops["w"])
            jax.block_until_ready(v)
            for fn, extra in steps:
                v = fn(v, *(ops[e] for e in extra))
                jax.block_until_ready(v)
            return v

        unfused()  # warm every jit
        t0 = time.perf_counter()
        iters = 5 if smoke else 10
        for _ in range(iters):
            unfused()
        t_unfused = (time.perf_counter() - t0) / iters

        # ---- model: fused Pallas plan vs schedule-aware unfused chain ----
        tiles = pick_tiles(m, k, n, jnp.float32)
        rep = fusion.graph_cost(graph, m, k, n, tiles=tiles, dtype=dt)
        unf = fusion.estimate_unfused(graph, m, k, n, dtype=dt, tiles=tiles)
        model_speedup = unf.total_time / rep.total_time
        bytes_ratio = unf.hbm_bytes / rep.hbm_bytes

        rows.append((
            f"fusion_bert_output_{m}x{k}x{n}",
            t_fused * 1e6,
            f"wall_fused_vs_unfused={t_unfused / t_fused:.2f}"
            f";model_fused_vs_unfused={model_speedup:.2f}"
            f";model_bytes_ratio={bytes_ratio:.2f}"
            f";spec={rep.spec};bound={rep.bound}",
        ))

        # ---- autotuned fused nest (model-ranked) -------------------------
        results = fusion.autotune_graph(graph, m, k, n, tiles=tiles,
                                        max_candidates=20 if smoke else 60)
        if results:
            best = results[0]
            rows.append((
                f"fusion_autotune_{m}x{k}x{n}",
                best.report.total_time * 1e6,
                f"best_spec={best.candidate.spec_string}"
                f";gflops={best.report.gflops:.0f}"
                f";candidates={len(results)}",
            ))

        if smoke:
            # parity vs the hand-written kernel (interpret mode).  The
            # oracle takes a keep-mask; feed it the exact keep decisions the
            # in-kernel PRNG regenerates (counter bits depend only on the
            # element coordinates, so the top-left slice is slice-invariant)
            from repro.kernels.fused_output import fused_output_ref
            sm, sk, sn = 64, 128, 256
            sops = {
                "x": ops["x"][:sm, :sk], "w": ops["w"][:sk, :sn],
                "bias": ops["bias"][:sn], "seed": ops["seed"],
                "residual": ops["residual"][:sm, :sn],
                "gamma": ops["gamma"][:sn], "beta": ops["beta"][:sn],
            }
            pal = fusion.compile(graph, path="pallas", tiles=(16, 32, 64),
                                 interpret=True)(**sops)
            mask = frng.keep_mask(ops["seed"], OUTPUT_DROPOUT_SALT,
                                  (sm, sn), rate=dropout)
            want = fused_output_ref(
                sops["x"], sops["w"], sops["bias"], sops["residual"],
                sops["gamma"], sops["beta"], keep_mask=mask,
                dropout_rate=dropout)
            err = float(np.max(np.abs(np.asarray(pal) - np.asarray(want))))
            assert err < 1e-4, f"fused Pallas vs hand-written oracle: {err}"
            rows.append((f"fusion_parity_{sm}x{sk}x{sn}", 0.0,
                         f"max_err_vs_handwritten={err:.2e}"))

    rows.extend(_dropout_rows(rng, smoke))
    rows.extend(_gated_mlp_rows(rng, smoke))
    rows.extend(_attention_rows(rng, smoke))
    rows.extend(_backward_rows(rng, smoke))
    rows.extend(_profiler_rows(smoke))
    return rows


ATTENTION_JSON_PATH = os.path.join(os.path.dirname(DROPOUT_JSON_PATH),
                                   "BENCH_fusion_attention.json")


def _attention_rows(rng, smoke):
    """Derived chained-root attention vs the reference and the retired
    hand-written kernel: wall on the XLA path (fused graph vs
    ``ops.attention``), perf-model cost of the chained nest, and (smoke)
    interpret-mode parity of the fused Pallas kernel against both
    ``ops.attention`` and ``_legacy_flash_attention_pallas`` in fp32 *and*
    bf16, causal and sliding-window.  Writes
    ``BENCH_fusion_attention.json``."""
    from repro.kernels import ops as kops
    from repro.kernels.flash_attention import _legacy_flash_attention_pallas

    rows = []
    b, h, hk, s, d = (1, 2, 1, 128, 64) if smoke else (2, 8, 2, 1024, 64)
    dt = np.float32
    q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(dt))
    k = jnp.asarray(rng.normal(size=(b, hk, s, d)).astype(dt))
    v = jnp.asarray(rng.normal(size=(b, hk, s, d)).astype(dt))
    iters = 5 if smoke else 10
    report = {"smoke": smoke, "shape": [b, h, hk, s, d], "variants": []}

    for variant, window in (("causal", None), ("window", s // 4)):
        fused_fn = jax.jit(lambda q_, k_, v_, _w=window: fusion.fused_attention_apply(
            q_, k_, v_, causal=True, window=_w, backend="xla", vjp=False))
        ref_fn = jax.jit(lambda q_, k_, v_, _w=window: kops.attention(
            q_, k_, v_, causal=True, window=_w, backend="xla"))
        t_fused = _bench(lambda: fused_fn(q, k, v), iters=iters)
        t_ref = _bench(lambda: ref_fn(q, k, v), iters=iters)

        # perf model of the chained nest at the per-(B, H) problem shape
        graph = fusion.fused_attention_graph(
            causal=True, window=window or 0, scale=1.0 / np.sqrt(d))
        tiles = pick_tiles(s, d, s, jnp.float32)
        rep = fusion.graph_cost(graph, s, d, s, tiles=tiles, dtype=dt)

        rows.append((
            f"fusion_attention_{variant}_{b}x{h}x{s}x{d}",
            t_fused * 1e6,
            f"wall_fused_vs_ref={t_ref / t_fused:.2f}"
            f";model_us_per_head={rep.total_time * 1e6:.1f}"
            f";spec={rep.spec};bound={rep.bound}",
        ))
        report["variants"].append({
            "variant": variant, "window": window,
            "wall_fused_us": t_fused * 1e6, "wall_ref_us": t_ref * 1e6,
            "model_us_per_head": rep.total_time * 1e6,
            "spec": rep.spec, "bound": rep.bound,
        })

        if smoke:
            # parity gate: derived graph (both backends) vs ops.attention vs
            # the retired hand-written kernel, fp32 and bf16
            want = np.asarray(ref_fn(q, k, v), np.float32)
            pal = fusion.fused_attention_apply(
                q, k, v, causal=True, window=window,
                backend="pallas_interpret", vjp=False)
            legacy = _legacy_flash_attention_pallas(
                q, k, v, causal=True, window=window, interpret=True)
            err_x = float(np.max(np.abs(np.asarray(fused_fn(q, k, v),
                                                   np.float32) - want)))
            err_p = float(np.max(np.abs(np.asarray(pal, np.float32) - want)))
            err_l = float(np.max(np.abs(np.asarray(legacy, np.float32)
                                        - want)))
            assert err_x < 1e-4, f"attention {variant} xla parity: {err_x}"
            assert err_p < 1e-4, f"attention {variant} pallas parity: {err_p}"
            assert err_l < 1e-4, f"attention {variant} legacy parity: {err_l}"

            qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
            pal_b = fusion.fused_attention_apply(
                qb, kb, vb, causal=True, window=window,
                backend="pallas_interpret", vjp=False)
            want_b = np.asarray(kops.attention(
                qb, kb, vb, causal=True, window=window, backend="xla"),
                np.float32)
            err_b = float(np.max(np.abs(np.asarray(pal_b, np.float32)
                                        - want_b)))
            assert err_b < 2e-2, f"attention {variant} bf16 parity: {err_b}"
            rows.append((
                f"fusion_attention_parity_{variant}_{b}x{h}x{s}x{d}", 0.0,
                f"max_err_xla={err_x:.2e};max_err_pallas={err_p:.2e}"
                f";max_err_vs_legacy={err_l:.2e};max_err_bf16={err_b:.2e}",
            ))
            report["variants"][-1].update(
                parity_err_xla=err_x, parity_err_pallas=err_p,
                parity_err_legacy=err_l, parity_err_bf16=err_b)

    with open(ATTENTION_JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)
    return rows


def _profiler_rows(smoke):
    """Model-vs-measured attribution over the fused library graphs
    (``repro.obs.profiler``): each graph gets a warmup+median wall-clock
    measurement on the XLA reference path beside its perf-model prediction.
    Records, relative drift flags, and the process-global ``fusion.*`` /
    ``tune.*`` counters accumulated by this benchmark run land in
    ``BENCH_fusion_profile.json``."""
    from repro.obs import profiler
    from repro.obs.metrics import default_registry

    rows = []
    m, k, n = (256, 512, 512) if smoke else (2048, 2048, 1024)
    graphs = [
        ("mlp_gelu", fusion.fused_mlp_graph("gelu")),
        ("gated_mlp_silu", fusion.fused_gated_mlp_graph("silu")),
        ("output_dropout", fusion.fused_output_graph(0.1)),
    ]
    records = []
    for name, g in graphs:
        rec = profiler.profile_graph(g, m, k, n, backend="xla",
                                     iters=3 if smoke else 5, warmup=1)
        records.append(rec)
        rows.append((
            f"fusion_profile_{name}_{m}x{k}x{n}",
            rec.measured_s * 1e6,
            f"predicted_us={rec.predicted_s * 1e6:.1f}"
            f";drift={rec.drift:.1f};bound={rec.bound};spec={rec.spec}",
        ))
    flags = profiler.drift_flags(records)
    snap = default_registry().snapshot()
    counters = {key: val for key, val in snap.items()
                if key.startswith(("fusion.", "tune."))}
    report = {
        "smoke": smoke,
        "shape": [m, k, n],
        "backend": "xla",
        "records": [r.to_dict() for r in records],
        "drift_flags": flags,
        "counters": counters,
    }
    with open(PROFILE_JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)
    return rows


def _dropout_rows(rng, smoke):
    """Mask-vs-PRNG dropout on the fused-output layer: the legacy graph
    streams a pre-generated (M, N) bool keep-mask through the nest (the one
    epilogue operand whose traffic grows with the output); ``dropout_rng``
    regenerates the bits in-kernel from a scalar seed.  Reports wall (XLA
    path, mask generation *included* in the mask wall — a real training step
    pays it every iteration), perf-model time, and the HBM traffic delta;
    writes ``BENCH_fusion_dropout.json``."""
    rows = []
    m, k, n = (256, 512, 512) if smoke else (4096, 4096, 1024)
    rate = 0.1
    dt = np.float32
    ops = {
        "x": jnp.asarray(rng.normal(size=(m, k)).astype(dt)),
        "w": jnp.asarray(rng.normal(size=(k, n)).astype(dt)),
        "bias": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
        "residual": jnp.asarray(rng.normal(size=(m, n)).astype(dt)),
        "gamma": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
        "beta": jnp.asarray(rng.normal(size=(n,)).astype(dt)),
    }
    g_mask = fusion.fused_output_graph(rate, rng_dropout=False)
    g_rng = fusion.fused_output_graph(rate)
    iters = 5 if smoke else 10

    mask_fn = jax.jit(lambda key, **o: fusion.compile(g_mask, path="xla")(
        keep_mask=jax.random.bernoulli(key, 1.0 - rate, (m, n)), **o))
    key = jax.random.PRNGKey(0)
    t_mask = _bench(lambda: mask_fn(key, **ops), iters=iters)

    rng_fn = jax.jit(lambda seed, **o: fusion.compile(g_rng, path="xla")(
        seed=seed, **o))
    seed = jnp.asarray(23, jnp.uint32)
    t_rng = _bench(lambda: rng_fn(seed, **ops), iters=iters)

    tiles = pick_tiles(m, k, n, jnp.float32)
    rep_mask = fusion.graph_cost(g_mask, m, k, n, tiles=tiles, dtype=dt)
    rep_rng = fusion.graph_cost(g_rng, m, k, n, tiles=tiles, dtype=dt)
    traffic_delta = rep_mask.hbm_bytes - rep_rng.hbm_bytes

    # acceptance: the PRNG graph lowers with NO (M, N) mask operand — its
    # traffic accounting must drop by at least the mask's footprint
    assert traffic_delta >= m * n, (rep_mask.hbm_bytes, rep_rng.hbm_bytes)
    assert all(o.kind != "mask"
               for o in fusion.simplify_graph(g_rng).operands)

    # parity: the PRNG draw is backend-bit-identical (keep decisions) and
    # close to the reference everywhere
    sm, sk, sn = (64, 128, 256)
    sops = {kk: (v[:sm, :sk] if kk == "x" else
                 v[:sk, :sn] if kk == "w" else
                 v[:sm, :sn] if kk == "residual" else v[:sn])
            for kk, v in ops.items()}
    ref = fusion.compile(g_rng, path="xla")(seed=seed, **sops)
    pal = fusion.compile(g_rng, path="pallas", tiles=(16, 32, 64),
                         interpret=True)(seed=seed, **sops)
    parity_err = float(np.max(np.abs(np.asarray(ref) - np.asarray(pal))))
    assert parity_err < 1e-4, f"mask-free PRNG parity: {parity_err}"

    rows.append((
        f"fusion_dropout_mask_vs_prng_{m}x{k}x{n}",
        t_rng * 1e6,
        f"wall_mask_vs_prng={t_mask / t_rng:.2f}"
        f";model_mask_vs_prng={rep_mask.total_time / rep_rng.total_time:.2f}"
        f";traffic_delta_mb={traffic_delta / 1e6:.2f}"
        f";parity_max_err={parity_err:.2e}",
    ))

    report = {
        "smoke": smoke,
        "shape": [m, k, n],
        "rate": rate,
        "scheme": frng.SCHEME,
        "wall_mask_us": t_mask * 1e6,
        "wall_prng_us": t_rng * 1e6,
        "wall_mask_vs_prng": t_mask / t_rng,
        "model_mask_s": rep_mask.total_time,
        "model_prng_s": rep_rng.total_time,
        "model_hbm_bytes_mask": rep_mask.hbm_bytes,
        "model_hbm_bytes_prng": rep_rng.hbm_bytes,
        "traffic_delta_bytes": traffic_delta,
        "parity_max_err": parity_err,
    }
    with open(DROPOUT_JSON_PATH, "w") as f:
        json.dump(report, f, indent=1)
    return rows


def _backward_rows(rng, smoke):
    """Fused-vs-unfused *backward*: wall (one jitted value_and_grad through
    ``compile_with_vjp``'s derived backward graphs vs XLA differentiating
    the composed reference), model (summed ``graph_cost`` of the derived
    backward TppGraphs vs their op-by-op estimates), and (smoke) cotangent
    parity of the interpret-mode Pallas backward against ``jax.grad`` of the
    XLA reference."""
    rows = []
    m, k, n = (256, 512, 512) if smoke else (4096, 4096, 1024)
    graph = fusion.fused_gated_mlp_graph("silu")
    dt = np.float32
    ops = {
        "x": jnp.asarray(rng.normal(size=(m, k)).astype(dt)),
        "wg": jnp.asarray(rng.normal(size=(k, n)).astype(dt)),
        "wu": jnp.asarray(rng.normal(size=(k, n)).astype(dt)),
    }
    probe = jnp.asarray(rng.normal(size=(m, n)).astype(dt))

    vjp_fn = fusion.compile_with_vjp(graph, "xla")
    ref_fn = fusion.compile(graph, path="xla")

    def loss(fn):
        return lambda o: jnp.sum(fn(**o).astype(jnp.float32) * probe)

    fused_step = jax.jit(jax.value_and_grad(loss(vjp_fn)))
    xla_step = jax.jit(jax.value_and_grad(loss(ref_fn)))
    iters = 5 if smoke else 10
    t_fused = _bench(lambda: fused_step(ops), iters=iters)
    t_xla = _bench(lambda: xla_step(ops), iters=iters)

    # model: every derived backward graph priced by the fused perf model vs
    # its own op-by-op chain (each gets its own graph_signature → its own
    # tune-cache entries); problem shapes come from the plan itself
    plan = fusion.derive_vjp(graph)
    bgraphs = plan.fused_graphs()
    t_model_fused = t_model_unf = 0.0
    for name, bg in bgraphs.items():
        bm_, bk_, bn_ = plan.problem_shape(name, m, k, n)
        tiles = pick_tiles(bm_, bk_, bn_, jnp.float32)
        rep = fusion.graph_cost(bg, bm_, bk_, bn_, tiles=tiles, dtype=dt)
        unf = fusion.estimate_unfused(bg, bm_, bk_, bn_, dtype=dt, tiles=tiles)
        t_model_fused += rep.total_time
        t_model_unf += unf.total_time
    rows.append((
        f"fusion_bwd_gated_mlp_{m}x{k}x{n}",
        t_fused * 1e6,
        f"wall_fwdbwd_fused_vs_xlagrad={t_xla / t_fused:.2f}"
        f";model_bwd_fused_vs_unfused={t_model_unf / t_model_fused:.2f}"
        f";bwd_graphs={len(bgraphs)}",
    ))

    if smoke:
        # cotangent parity: interpret-mode Pallas backward kernels vs
        # jax.grad of the composed-TPP XLA reference
        sm, sk, sn = 64, 128, 256
        sops = {"x": ops["x"][:sm, :sk], "wg": ops["wg"][:sk, :sn],
                "wu": ops["wu"][:sk, :sn]}
        sprobe = probe[:sm, :sn]
        pal_fn = fusion.compile_with_vjp(graph, "pallas_interpret")

        def sloss(fn):
            return lambda o: jnp.sum(fn(**o).astype(jnp.float32) * sprobe)

        g_ref = jax.grad(sloss(ref_fn))(sops)
        g_pal = jax.grad(sloss(pal_fn))(sops)
        err = max(float(np.max(np.abs(np.asarray(g_ref[kk]) -
                                      np.asarray(g_pal[kk]))))
                  for kk in sops)
        assert err < 1e-3, f"fused Pallas backward vs jax.grad oracle: {err}"
        rows.append((f"fusion_bwd_parity_{sm}x{sk}x{sn}", 0.0,
                     f"max_cotangent_err_vs_jaxgrad={err:.2e}"))
    return rows


def _gated_mlp_rows(rng, smoke):
    """Multi-root showcase: the two-root gated-MLP graph vs the unfused
    three-op chain (two GEMMs + act/mul combine), wall + model + (smoke)
    interpret-mode Pallas parity."""
    rows = []
    m, k, n = (256, 512, 512) if smoke else (4096, 4096, 4096)
    graph = fusion.fused_gated_mlp_graph("silu")
    dt = np.float32
    x = jnp.asarray(rng.normal(size=(m, k)).astype(dt))
    wg = jnp.asarray(rng.normal(size=(k, n)).astype(dt))
    wu = jnp.asarray(rng.normal(size=(k, n)).astype(dt))

    fused_fn = jax.jit(fusion.compile(graph, path="xla"))
    t_fused = _bench(lambda: fused_fn(x=x, wg=wg, wu=wu),
                     iters=5 if smoke else 10)

    gemm = jax.jit(lambda a, b: jnp.dot(a, b,
                                        preferred_element_type=jnp.float32))
    act = jax.jit(fusion.EPILOGUE_OPS["silu"].apply)
    mul = jax.jit(fusion.EPILOGUE_OPS["mul"].apply)

    def unfused():
        g = gemm(x, wg)
        jax.block_until_ready(g)
        u = gemm(x, wu)
        jax.block_until_ready(u)
        a = act(g)
        jax.block_until_ready(a)
        return mul(a, u)

    t_unfused = _bench(unfused, iters=5 if smoke else 10)

    tiles = pick_tiles(m, k, n, jnp.float32)
    rep = fusion.graph_cost(graph, m, k, n, tiles=tiles, dtype=dt)
    unf = fusion.estimate_unfused(graph, m, k, n, dtype=dt, tiles=tiles)
    rows.append((
        f"fusion_gated_mlp_{m}x{k}x{n}",
        t_fused * 1e6,
        f"wall_fused_vs_unfused={t_unfused / t_fused:.2f}"
        f";model_fused_vs_unfused={unf.total_time / rep.total_time:.2f}"
        f";model_bytes_ratio={unf.hbm_bytes / rep.hbm_bytes:.2f}"
        f";spec={rep.spec};bound={rep.bound}",
    ))

    if smoke:
        # parity: one two-root Pallas nest vs the unfused chain
        sm, sk, sn = 64, 128, 256
        pal = fusion.compile(graph, path="pallas", tiles=(16, 32, 64),
                             interpret=True)(
            x=x[:sm, :sk], wg=wg[:sk, :sn], wu=wu[:sk, :sn])
        ref = fusion.compile(graph, path="xla")(
            x=x[:sm, :sk], wg=wg[:sk, :sn], wu=wu[:sk, :sn])
        err = float(np.max(np.abs(np.asarray(pal) - np.asarray(ref))))
        assert err < 1e-3, f"two-root fused Pallas vs unfused chain: {err}"
        rows.append((f"fusion_gated_parity_{sm}x{sk}x{sn}", 0.0,
                     f"max_err_vs_unfused={err:.2e}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + interpret-mode parity check")
    args = ap.parse_args()
    for r in run(smoke=args.smoke):
        print(",".join(map(str, r)))
