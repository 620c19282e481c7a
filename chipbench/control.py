"""The readings that a cell's limits are set from, on the chip, in one
process: the program's numbers on each of ``--seeds`` (the lower reading of
each is their largest), and on each of ``--control-seeds`` the control's,
the reference in the precision below the cell's put in the program's place
(float8 weights serving, bfloat16 state training; the upper reading is
their smallest), and for a training cell also the reference with half of
each batch left out.  Each side is judged by the harness's own decision,
``bench.passes``, under the cell's own limits.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 1,2,3

Each seed runs the cell's own traffic at its own load for ``--seconds``
and prints one JSON line, each side with its numbers and ``correct``; the
last line holds the readings.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
SIDES = ("program", "control", "half_batch")


def main(argv=None) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.lib import bench

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    a = p.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    control = [int(s) for s in a.control_seeds.split(",") if s]
    cell = bench.load_cell(ROOT, a.workload)
    devs = bench.require_chips(cell.chips)
    bench.enable_compile_cache(ROOT)
    clock = bench.CompileClock()
    driver = bench.driver(cell.mix["kind"])
    lower, upper, verdicts = {}, {}, {}
    for seed in seeds + [s for s in control if s not in seeds]:
        args = bench.parse_args(["--workload", a.workload, "--seed",
                                 str(seed), "--seconds", str(a.seconds)])
        r = driver.limit_readings(cell, args, devs, clock, seed in control)
        line = {"seed": seed, "values": r.get("values")}
        for side in SIDES:
            if side not in r or (side == "program" and seed not in seeds):
                continue
            checks = r[side]
            ok = bench.passes(checks)
            line[side] = {"correct": ok,
                          **{k: c["value"] for k, c in checks.items()}}
            verdicts.setdefault(side, []).append(ok)
            into = lower if side == "program" else upper
            for k, c in checks.items():
                into.setdefault(f"{side}.{k}", []).append(c["value"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": a.workload,
        "correct": verdicts,
        "lower": {k: max(v) for k, v in lower.items()},
        "upper": {k: min(v) for k, v in upper.items()},
        "readings": {**lower, **upper}}), flush=True)


if __name__ == "__main__":
    main()
