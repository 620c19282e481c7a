"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  The cell (an entry of ``BENCHMARK.json``)
names its configuration and traffic files; see ``chipbench/lib/bench.py``.
With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profile of a few
seconds at the end of the window.  The last line of standard output is
one JSON object; the numbers that decide ``correct`` are printed beside
their limits as the last lines of standard error.

The process exits non-zero, with no result line, when JAX finds no TPU or
fewer chips than the cell asks for, or when the checkout lacks the
program under ``src/``.
"""
from __future__ import annotations

import os
import pathlib
import sys
import time

T_PROC0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
# libtpu logs into the run's checkout, not a fixed path of the machine
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.lib import bench

    args = bench.parse_args(argv)
    cell = bench.load_cell(ROOT, args.workload)
    devs = bench.require_chips(cell.chips)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chipbench: no program under {ROOT / 'src'}")
    cache = bench.enable_compile_cache(ROOT)
    clock = bench.CompileClock()
    bench.say(f"cell {cell.name} seed {args.seed}: {devs[0].device_kind} "
              f"x{len(devs)}, compile cache {cache}")
    driver = bench.driver(cell.mix["kind"])
    result, checks = driver.run(cell, args, devs, clock, T_PROC0)
    bench.emit(result, checks)


if __name__ == "__main__":
    main()
