"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-program device time (and the calls of each program that lie wholly
in the window), the top device operations and the idle gaps by what the
host was doing.

The traced window is the host span named ``bench.window`` that the harness
opens around the steps it profiles.  On each TPU plane, busy time is the
union of the ``XLA Ops`` events inside the window; a program's time is the
sum of its ``XLA Modules`` events (``jit__decode_segment(…)`` → key
``_decode_segment``).  Each idle gap of at least ``MIN_GAP`` seconds is
named by the innermost host event that spans the gap's midpoint; shorter
gaps, between the operations of one program, are summed under
``SHORT_GAPS``.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
import shutil
import time

WINDOW = "bench.window"
TRACE_DIR = ".chipbench_trace"      # under the checkout; removed after use
TRACE_SECONDS = 4.0
HOST_GUARD_S = 2.0
MIN_GAP = 10e-6
SHORT_GAPS = "between device ops (< 10 us)"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds
    end: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over the device planes
    programs: dict                          # program → device seconds
    calls: dict                             # program → [calls, seconds] of
    #                                         those wholly inside the window
    ops: dict                               # device op → seconds, all
    device_ops: list                        # [[name, seconds]] top 10
    idle_gaps: list                         # [[host activity, seconds]]
    devices: int


def program_key(module: str) -> str:
    """``jit__decode_segment(12)`` → ``_decode_segment``."""
    name = _SUFFIX.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path):
    """→ (device planes {name: {line: [Event]}}, host events [Event])."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name[len("/device:TPU:"):]:
            devices[plane.name] = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return devices, host


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, w0, w1):
    return [(max(e.start, w0), min(e.end, w1)) for e in events
            if e.end > w0 and e.start < w1]


class _HostIndex:
    """Host events binned by millisecond, to find those around a time."""

    def __init__(self, host, w0):
        self.w0, self.bins = w0, collections.defaultdict(list)
        for e in host:
            if e.name != WINDOW:
                for b in range(self._bin(e.start), self._bin(e.end) + 1):
                    self.bins[b].append(e)

    def _bin(self, t) -> int:
        return int((t - self.w0) * 1e3)

    def label(self, t) -> str:
        inner = None
        for e in self.bins.get(self._bin(t), ()):
            if e.start <= t <= e.end and (
                    inner is None
                    or e.end - e.start < inner.end - inner.start):
                inner = e
        return inner.name if inner is not None else "no host activity"


def summarize(devices: dict, host: list, top: int = 10) -> Summary:
    windows = [e for e in host if e.name == WINDOW]
    if not windows or not devices:
        raise ValueError("trace holds no traced window or no TPU plane")
    w0, w1 = windows[0].start, windows[0].end
    near = _HostIndex([e for e in host if e.end > w0 and e.start < w1], w0)
    busy_total = 0.0
    programs = collections.Counter()
    calls = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.Counter()
    gaps = collections.Counter()
    for lines in devices.values():
        op_events = lines.get("XLA Ops") or [
            e for name, evs in lines.items() if name != "Steps" for e in evs]
        busy = union(_clip(op_events, w0, w1))
        busy_total += sum(e - s for s, e in busy)
        for e in op_events:
            if e.end > w0 and e.start < w1:
                ops[_SUFFIX.sub("", e.name)] += min(e.end, w1) - max(
                    e.start, w0)
        for e in lines.get("XLA Modules", []):
            if e.end > w0 and e.start < w1:
                programs[program_key(e.name)] += min(e.end, w1) - max(
                    e.start, w0)
                if w0 <= e.start and e.end <= w1:
                    c = calls[program_key(e.name)]
                    c[0] += 1
                    c[1] += e.end - e.start
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= MIN_GAP:
                gaps[near.label((s + e) / 2)] += e - s
            elif e > s:
                gaps[SHORT_GAPS] += e - s
    n = len(devices)
    return Summary(
        window_s=w1 - w0, busy_s=busy_total / n,
        programs={k: v / n for k, v in programs.items()},
        calls={k: [c / n, t / n] for k, (c, t) in calls.items()},
        ops={k: v / n for k, v in ops.items()},
        device_ops=[[k, v / n] for k, v in ops.most_common(top)],
        idle_gaps=[[k, v / n] for k, v in gaps.most_common(top)],
        devices=n)


def reduce_file(path) -> Summary:
    return summarize(*load(path))


class Profile:
    """The profiler over the last ``TRACE_SECONDS`` of a window of
    ``seconds`` that opens at ``t_start``, under a host span named
    ``WINDOW``, with the Python tracer off.  ``tick(now)`` starts it when
    due; the driver calls ``stop()`` as the window closes;
    ``summary()`` reduces what it wrote, then removes it.

    Starting and stopping the profiler hold the one host thread that also
    drives the load (stopping writes the trace), so host-clock readings of
    a traced run are taken from the part of the window that ends
    ``HOST_GUARD_S`` before ``t_on``.  ``held_s`` keeps how long each call
    held the host."""

    def __init__(self, root, t_start: float, seconds: float):
        self.dir = pathlib.Path(root) / TRACE_DIR
        shutil.rmtree(self.dir, ignore_errors=True)
        span = min(TRACE_SECONDS, seconds / 2)
        self.t_on = t_start + seconds - span
        self.on = self.done = False
        self._span = None
        self.held_s = {}

    def host_until(self) -> float:
        """Requests due before this are clear of the profiler's calls."""
        return self.t_on - HOST_GUARD_S

    def tick(self, now: float) -> None:
        import jax
        if not (self.on or self.done) and now >= self.t_on:
            t0 = time.perf_counter()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=options)
            self._span = jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()
            self.on = True
            self.held_s["start"] = time.perf_counter() - t0

    def stop(self) -> None:
        import jax
        if self.on:
            t0 = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True
            self.held_s["stop"] = time.perf_counter() - t0

    def summary(self) -> Summary:
        self.stop()
        try:
            return reduce_file(sorted(self.dir.rglob("*.xplane.pb"))[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
