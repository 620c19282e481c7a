"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).
Prints ``name,us_per_call,derived`` CSV rows.  Modules may additionally write
machine-readable artifacts (``bench_autotune`` → ``BENCH_autotune.json`` at
the repo root: configs/sec, generated vs scored vs pruned candidate counts,
analytic-vs-trace model agreement) so perf trajectories are tracked PR over
PR; such modules advertise the path via a ``JSON_PATH`` attribute."""
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "bench_gemm",        # Figs. 2/4/5
    "bench_mlp",         # Fig. 3
    "bench_perfmodel",   # Fig. 6
    "bench_conv",        # Fig. 7 / Table II
    "bench_spmm",        # Fig. 8
    "bench_e2e",         # Figs. 9/10/11, Table I
    "bench_autotune",    # §V-A2 tuning cost
]


def main() -> None:
    failures = 0
    print("name,us_per_call,derived")
    for name in MODULES:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
            artifact = getattr(mod, "JSON_PATH", None)
            if artifact:
                print(f"# {name}: wrote {artifact}", file=sys.stderr)
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
