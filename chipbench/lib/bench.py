"""What every cell shares: finding a cell's files by name, the chip check,
the compile cache, the compile clock and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
is a file of its own, found by name:

    chipbench/configs/<config>.json
    chipbench/traffic/<traffic>.json
    chipbench/metrics/<quantity>.py    (one reader per quantity)

so a later cell, mix or metric is a new file and a new entry, never an
edit of a file that is already here.  A per-layer metric's reader is the
file of its whole name, or else of its name before the cell's suffix:
``device_idle.serve`` and ``device_idle.train`` are both read by
``metrics/device_idle.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH_DIR = "chipbench"


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list        # metric entries that this cell reports
    per_layer: list
    root: pathlib.Path


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def find_file(root: pathlib.Path, kind: str, name: str,
              suffixes=(".json",)) -> pathlib.Path:
    """``<root>/chipbench/<kind>/<name><suffix>``; exactly one must exist."""
    base = root / BENCH_DIR / kind
    hits = [base / f"{name}{s}" for s in suffixes if (base / f"{name}{s}")
            .is_file()]
    if len(hits) != 1:
        raise FileNotFoundError(
            f"{kind} {name!r}: want one of {[f'{name}{s}' for s in suffixes]}"
            f" under {base}, found {len(hits)}")
    return hits[0]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = json.loads(find_file(root, "configs", w["config"]).read_text())
    mix = json.loads(find_file(root, "traffic", w["traffic"]).read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    unlisted = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    if unlisted:
        raise ValueError(f"per-layer metrics {unlisted} list no 'workloads'")
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_reader(root: pathlib.Path, metric: str):
    """The per-layer metric's reader: ``chipbench/metrics/<metric>.py``, or
    else the file of the name with its last ``.<suffix>`` taken off, and so
    on.  Its ``read(r)`` takes the run's readings (the driver's ``readings``
    says what they hold) and returns a number, or None where the run gave
    it nothing to read."""
    name = metric
    while not (root / BENCH_DIR / "metrics" / f"{name}.py").is_file() \
            and "." in name:
        name = name.rsplit(".", 1)[0]
    path = find_file(root, "metrics", name, suffixes=(".py",))
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    """The module that runs a mix of ``kind``: ``chipbench/lib/<kind>.py``
    (``serve``, ``train``), whose ``run(cell, args, devs, clock, t0)``
    returns the result line and the checks."""
    return importlib.import_module(f"chipbench.lib.{kind}")


def require_chips(chips: int):
    """The devices JAX finds; exits non-zero unless they are at least
    ``chips`` TPUs.  Nothing falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: needs a TPU, but JAX found platform "
                 f"{devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path (the path is part of the cache's key), unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program is kept."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileClock:
    """Counts XLA compiles and sums their seconds (persistent-cache reads
    included) from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class GcClock:
    """The pauses of Python's garbage collector, from ``gc.callbacks``:
    ``settle()`` ends set-up with a full collection and freezes what
    survives, so the window's collections do not walk set-up's objects
    (compiled programs, traced graphs); the pauses after it are kept
    until ``close()``."""

    def __init__(self):
        self.pauses, self._t0, self._on = [], None, False
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._on and self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def settle(self) -> None:
        gc.collect()
        gc.freeze()
        self._on = True

    def close(self) -> str:
        """Stops counting; → a line that says what the pauses were."""
        gc.callbacks.remove(self._event)
        longest = max(self.pauses, key=lambda p: p[1], default=(None, 0.0))
        return (f"python gc after set-up: {len(self.pauses)} collections, "
                f"{1e3 * sum(p[1] for p in self.pauses):.1f} ms in all, "
                f"longest {1e3 * longest[1]:.1f} ms (generation "
                f"{longest[0]})")


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics;
    +inf entries (requests that never answered) sort last."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_record(devs, peak_bytes: Optional[int]) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


def peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def check(value, limit, at_least=False) -> dict:
    """One compared number beside its limit: it passes at or under the
    limit, or with ``at_least`` at or over it."""
    c = {"value": value, "limit": limit}
    if at_least:
        c["at_least"] = True
    return c


def passes(checks: dict) -> bool:
    """The decision ``correct``: every compared number is finite and on its
    side of its limit."""
    for c in checks.values():
        v = c["value"]
        if v is None or not math.isfinite(v):
            return False
        if (v < c["limit"]) if c.get("at_least") else (v > c["limit"]):
            return False
    return True


def emit(result: dict, checks: dict) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line of standard output, with the
    comparison under its own key, last."""
    for name, c in checks.items():
        side = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']!r} limit {side} {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
