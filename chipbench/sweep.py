"""Find an open-loop serving cell's knee on the chip: serve its mix at each
of ``--rates`` (requests/s) in one process and print, per rate, the tails
and whether the waiting queue grew across the window.

    python3 chipbench/sweep.py --workload <cell> --seconds 30 \
        --rates 2,3,4,5 --seed 1

The knee is the highest rate at which the queue does not grow; the cell's
mix file then carries 0.8 × knee as a number.  The benchmark's own runs
never sweep.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.lib import bench, serve

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=1)
    a = p.parse_args(argv)
    cell = bench.load_cell(ROOT, a.workload)
    devs = bench.require_chips(cell.chips)
    bench.enable_compile_cache(ROOT)
    clock = bench.CompileClock()
    for rate in (float(x) for x in a.rates.split(",")):
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        args = bench.parse_args(["--workload", a.workload, "--seed",
                                 str(a.seed), "--seconds", str(a.seconds)])
        s = serve.serve(c, args, devs, clock, time.perf_counter())
        r = s.readings
        print(json.dumps({
            "rate_per_s": rate, **s.values, "failed": s.failed,
            "waiting_first_third": r.get("window.waiting_first_third"),
            "waiting_last_third": r.get("window.waiting_last_third"),
            "slot_occupancy": 100.0 * r["window.decode_tokens"] / max(
                1, r["window.decode_iterations"] * r["num_slots"])}),
            flush=True)


if __name__ == "__main__":
    main()
