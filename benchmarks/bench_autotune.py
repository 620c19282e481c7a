"""Paper §V-A2 — autotuning cost: configurations searched per second
(the paper searches ~1000 'outer loop' configs in 2s–22min and is 2.3–500×
faster than TVM because the search stops at the TPP boundary).

Benchmarks the streaming search pipeline (lazy generation + bound pruning +
batched scoring, docs/autotuning.md) against the materialize-and-plan
exhaustive baseline it replaced, and verifies *equal candidate quality*: the
top-ranked spec string for the 32³-block GEMM and for every fusion library
graph must be identical under both strategies.  Emits a machine-readable
``BENCH_autotune.json`` (configs/sec, generated vs scored vs pruned counts,
analytic-vs-trace model agreement) so the perf trajectory is tracked PR over
PR; ``--smoke`` runs a reduced problem and exits non-zero on any equality
violation without touching the JSON artifact.
"""
import argparse
import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.core import LoopSpec, TensorMap, autotune, perf_model
from repro.launch.compile_cache import enable_compile_cache

JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_autotune.json")


def _gemm_inputs(nb: int, tile: int):
    loops = [LoopSpec(0, nb, 1, name="K"),
             LoopSpec(0, nb, 1, name="M"),
             LoopSpec(0, nb, 1, name="N")]
    in_maps = [TensorMap(("b", "a"), (tile, tile), layout="flat"),
               TensorMap(("a", "c"), (tile, tile), layout="flat")]
    out_map = TensorMap(("b", "c"), (tile, tile), layout="flat")
    kw = dict(dtype=jnp.bfloat16, flops_per_body=2 * tile ** 3,
              tile_mnk=(tile, tile, tile), reduction_letters=("a",),
              parallel_letters=("b", "c"), use_cache=False)
    return loops, in_maps, out_map, kw


def _bench_gemm(smoke: bool):
    nb = 8 if smoke else 32
    loops, in_maps, out_map, kw = _gemm_inputs(nb, 128)

    t0 = time.perf_counter()
    ex, exs = autotune.autotune_with_stats(
        loops, in_maps, out_map, strategy="exhaustive",
        max_candidates=None, top_k=16, **kw)
    dt_ex = time.perf_counter() - t0
    base_cps = exs.candidates_scored / dt_ex

    t0 = time.perf_counter()
    st, sts = autotune.autotune_with_stats(
        loops, in_maps, out_map, strategy="streaming",
        max_candidates=None, top_k=16, **kw)
    dt_st = time.perf_counter() - t0
    new_cps = sts.considered / dt_st

    return {
        "nb": nb,
        "baseline": {
            "strategy": "exhaustive",
            "configs": exs.candidates_scored,
            "total_s": round(dt_ex, 4),
            "configs_per_s": round(base_cps, 1),
        },
        "streaming": {
            "strategy": "streaming",
            "configs_considered": sts.considered,
            "generated": sts.candidates_generated,
            "scored": sts.candidates_scored,
            "pruned": sts.candidates_pruned,
            "families_pruned": sts.families_pruned,
            "total_s": round(dt_st, 4),
            "configs_per_s": round(new_cps, 1),
        },
        "speedup": round(new_cps / base_cps, 2),
        "top_spec_exhaustive": ex[0].candidate.spec_string,
        "top_spec_streaming": st[0].candidate.spec_string,
        "top_spec_match":
            ex[0].candidate.spec_string == st[0].candidate.spec_string,
    }, st


def _bench_graphs(smoke: bool):
    from repro import fusion

    cases = [
        ("fused_output", fusion.fused_output_graph(0.0)),
        ("fused_mlp_gelu", fusion.fused_mlp_graph()),
    ]
    m, k, n = (64, 64, 128) if smoke else (128, 128, 256)
    tiles = (16, 32, 64)
    out = {}
    for name, g in cases:
        ex = fusion.autotune_graph(g, m, k, n, tiles=tiles,
                                   max_candidates=None,
                                   strategy="exhaustive", use_cache=False)
        t0 = time.perf_counter()
        st, sts = fusion.autotune_graph(g, m, k, n, tiles=tiles,
                                        max_candidates=None,
                                        strategy="streaming", use_cache=False,
                                        return_stats=True)
        dt = time.perf_counter() - t0
        out[name] = {
            "top_spec_exhaustive": ex[0].candidate.spec_string,
            "top_spec_streaming": st[0].candidate.spec_string,
            "top_spec_match":
                ex[0].candidate.spec_string == st[0].candidate.spec_string,
            "scored": sts.candidates_scored,
            "filtered": sts.candidates_filtered,
            "total_s": round(dt, 4),
        }
    return out


def _model_vs_trace(results, nb: int):
    """Re-score the analytic top-5 with the trace oracle (the paper-faithful
    LRU walk) and report ranking agreement."""
    loops, in_maps, out_map, kw = _gemm_inputs(nb, 128)
    rows = {}
    for r in results[:5]:
        tl = autotune.cached_threaded_loop(
            r.candidate.loops, r.candidate.spec_string,
            reduction_letters=("a",))
        rep = perf_model.predict(
            tl.nest, in_maps, out_map, dtype=jnp.bfloat16,
            flops_per_body=2 * 128 ** 3, tile_mnk=(128, 128, 128),
            reduction_letters=("a",), mode="trace")
        rows[r.candidate.spec_string] = {
            "analytic_gflops": round(r.score, 2),
            "trace_gflops": round(rep.gflops, 2),
        }
    analytic_best = results[0].candidate.spec_string
    trace_best = max(rows, key=lambda s: rows[s]["trace_gflops"])
    return {
        "top1_match": rows[analytic_best]["trace_gflops"]
        >= rows[trace_best]["trace_gflops"] * (1 - 1e-9),
        "analytic_best": analytic_best,
        "trace_best": trace_best,
        "top5": rows,
    }


def run(smoke: bool = False):
    gemm, st_results = _bench_gemm(smoke)
    graphs = _bench_graphs(smoke)
    report = {
        "smoke": smoke,
        "gemm": gemm,
        "graphs": graphs,
    }
    if not smoke:
        report["model_vs_trace"] = _model_vs_trace(st_results, gemm["nb"])
        with open(JSON_PATH, "w") as f:
            json.dump(report, f, indent=1)

    ok = gemm["top_spec_match"] and all(
        g["top_spec_match"] for g in graphs.values())
    if not ok:
        raise AssertionError(
            f"streaming search diverged from exhaustive baseline: {report}")

    n_new = gemm["streaming"]["configs_considered"]
    dt_new = gemm["streaming"]["total_s"]
    rows = [
        ("autotune_exhaustive_baseline",
         gemm["baseline"]["total_s"] * 1e6 / gemm["baseline"]["configs"],
         f"configs={gemm['baseline']['configs']};"
         f"configs_per_s={gemm['baseline']['configs_per_s']:.0f}"),
        ("autotune_1000_configs",
         dt_new * 1e6 / max(n_new, 1),
         f"configs={n_new};"
         f"configs_per_s={gemm['streaming']['configs_per_s']:.0f};"
         f"speedup_vs_exhaustive={gemm['speedup']};"
         f"top_spec_match={gemm['top_spec_match']}"),
        ("autotune_fusion_graphs",
         sum(g["total_s"] for g in graphs.values()) * 1e6 / len(graphs),
         f"graphs={len(graphs)};"
         f"top_spec_match={all(g['top_spec_match'] for g in graphs.values())}"),
    ]
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="reduced sizes, equality checks only, no JSON")
    args = p.parse_args()
    try:
        for r in run(smoke=args.smoke):
            print(",".join(map(str, r)))
    except AssertionError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        sys.exit(1)
    if args.smoke:
        print("bench_autotune --smoke: OK")
