"""Device time by named scope, and idle time by engine phase, from a
profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives each device operation's interval but
not the name path that its HLO metadata carries (the ``tf_op`` stat of
the event's metadata, such as
``jit(_decode_segment)/while/body/lm_head/dot_general``).  ``read_planes``
reads both from the XSpace protobuf itself, in plain Python.  The program
opens ``jax.named_scope`` around its kernels (``ops.<function>``) and
layers (``attention``, ``mlp``, ``lm_head``, ``sample``), so a scope's
device time is the union of the intervals of the operations whose own
path names it, clipped to the traced window.  The union, not the sum:
operations nest (a ``while`` encloses its body's operations).

Each idle gap of the device of at least ``trace.MIN_GAP`` seconds is
named by the innermost ``engine.*`` or ``bench.*`` host span at its
midpoint: the engine's phases (``repro.obs.trace``) and the harness's.

    python3 -m chipbench.lib.scopes <trace.xplane.pb>

from the root of a checkout prints each program's scopes and the idle
phases of the trace's window (the host span ``trace.WINDOW``).
"""
from __future__ import annotations

import collections
import dataclasses
import re
import sys

from chipbench.lib import trace as trace_lib

OPS_LINE = "XLA Ops"
_TPU = "/device:TPU:"
PHASES = ("engine.", "bench.")
NO_PHASE = "no engine or bench span"
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


@dataclasses.dataclass
class Op:
    start: float        # seconds, on ProfileData's clock
    end: float
    path: str           # the tf_op name path ("" where it has none)


# -- the XSpace protobuf, field by field ---------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: an int for a varint, a slice of
    ``buf`` for a length-delimited field, raw bytes for fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} is not in an XSpace")
        yield key >> 3, v


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane(buf, want_lines):
    """XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5."""
    name, lines, events, stats = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, meta = _map_entry(v)
            events[key] = meta
        elif f == 5:
            key, meta = _map_entry(v)
            stats[key] = bytes(dict(_fields(meta)).get(2, b"")).decode()
    tf_op = {v: k for k, v in stats.items()}.get("tf_op")
    paths = {}
    for key, meta in events.items():
        path = ""
        for f, v in _fields(meta):          # XEventMetadata: stats 5
            if f != 5:
                continue
            stat = dict(_fields(v))         # XStat: id 1, str 5, ref 7
            if stat.get(1) == tf_op:
                path = (bytes(stat[5]).decode() if 5 in stat
                        else stats.get(stat.get(7), ""))
        paths[key] = path
    out = {}
    for ln in lines if want_lines else ():
        line = {f: v for f, v in _fields(ln) if f != 4}
        if bytes(line.get(2, b"")).decode() != OPS_LINE:
            continue
        t0 = line.get(3, 0) * 1e-9          # XLine: timestamp_ns 3
        ops = out.setdefault(OPS_LINE, [])
        for f, ev in _fields(ln):
            if f != 4:
                continue
            e = dict(_fields(ev))           # XEvent: id 1, offset 2, dur 3
            start = t0 + e.get(2, 0) * 1e-12
            ops.append(Op(start, start + e.get(3, 0) * 1e-12,
                          paths.get(e.get(1, 0), "")))
    return name, paths, out


def read_planes(path, with_ops=True) -> dict:
    """→ {TPU plane name: {"paths": {event metadata id: tf_op path},
    "ops": [Op] of its ``XLA Ops`` line}}."""
    buf = memoryview(open(path, "rb").read())
    planes = {}
    for f, v in _fields(buf):               # XSpace: planes 1
        if f != 1:
            continue
        name = bytes(dict(_fields(v)).get(2, b"")).decode()
        if not (name.startswith(_TPU)
                and "Core" not in name[len(_TPU):]):
            continue
        name, paths, lines = _plane(v, with_ops)
        planes[name] = {"paths": paths, "ops": lines.get(OPS_LINE, [])}
    return planes


def tf_op_paths(path) -> dict:
    """{TPU plane name: [tf_op path of each event metadata that has one]}."""
    return {name: [p for p in plane["paths"].values() if p]
            for name, plane in read_planes(path, with_ops=False).items()}


# -- reductions ----------------------------------------------------------

def components(path: str) -> tuple[str, set]:
    """→ (program, the scope names on the path).  Autodiff and calls wrap a
    scope (``transpose(jvp(attention))``); the wrapper is peeled off."""
    parts = re.split(r"[/;]", path)
    names = set()
    for part in parts:
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        names.add(part)
    head = _WRAPPED.match(parts[0])
    program = head.group(1) if head else parts[0]
    names.discard(program)
    return program, names


def _seconds(intervals, w0, w1) -> float:
    return sum(e - s for s, e in trace_lib.union(
        [(max(s, w0), min(e, w1)) for s, e in intervals
         if e > w0 and s < w1]))


def leaves(ops) -> list:
    """The operations that enclose no other: not a ``while`` around its
    body's operations."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, op in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or not (nxt.start < op.end and nxt.end <= op.end):
            out.append(op)
    return out


def scopes(planes: dict, w0: float, w1: float) -> dict:
    """{program: {scope: device seconds}} in the window ``[w0, w1]``, each
    the union of its operations' intervals, the mean over the planes."""
    spans = collections.defaultdict(lambda: collections.defaultdict(list))
    for plane in planes.values():
        for op in plane["ops"]:
            if op.path and op.end > w0 and op.start < w1:
                program, names = components(op.path)
                for name in names:
                    spans[program][name].append((op.start, op.end))
    n = max(len(planes), 1)
    return {prog: {name: _seconds(iv, w0, w1) / n
                   for name, iv in by_name.items()}
            for prog, by_name in spans.items()}


def named_share(planes: dict, w0: float, w1: float, program: str,
                names) -> float:
    """Share of ``program``'s leaf-operation time in the window that lies
    under one of the scopes ``names``."""
    names = set(names)
    inside, total = [], []
    for plane in planes.values():
        for op in leaves(plane["ops"]):
            if not op.path or not (op.end > w0 and op.start < w1):
                continue
            prog, comps = components(op.path)
            if prog != program:
                continue
            total.append((op.start, op.end))
            if comps & names:
                inside.append((op.start, op.end))
    t = _seconds(total, w0, w1)
    return _seconds(inside, w0, w1) / t if t else 0.0


def _phase(host, t) -> str:
    inner = None
    for e in host:
        if e.start <= t <= e.end and (
                inner is None or e.end - e.start < inner.end - inner.start):
            inner = e
    return inner.name if inner is not None else NO_PHASE


def window(host: list) -> tuple[float, float]:
    """The first ``trace.WINDOW`` host span's start and end."""
    w = next(e for e in host if e.name == trace_lib.WINDOW)
    return w.start, w.end


def idle_phases(devices: dict, host: list, top: int = 10) -> list:
    """[[phase, seconds]] of the traced window's idle gaps of at least
    ``trace.MIN_GAP``, each under the innermost ``engine.*``/``bench.*``
    host span at its midpoint, the mean over the planes; ``devices`` and
    ``host`` as ``trace.load`` gives them."""
    w0, w1 = window(host)
    spans = [e for e in host if e.name.startswith(PHASES)
             and e.name != trace_lib.WINDOW and e.end > w0 and e.start < w1]
    gaps = collections.Counter()
    for lines in devices.values():
        busy = trace_lib.union(trace_lib._clip(lines.get(OPS_LINE, []),
                                               w0, w1))
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= trace_lib.MIN_GAP:
                mid = (s + e) / 2
                gaps[_phase([h for h in spans if h.start <= mid <= h.end],
                            mid)] += e - s
    n = max(len(devices), 1)
    return [[k, v / n] for k, v in gaps.most_common(top)]


def main(path: str) -> None:
    devices, host = trace_lib.load(path)
    w0, w1 = window(host)
    planes = read_planes(path)
    print(f"window {w1 - w0:.6f} s; tf_op paths per plane:",
          {k: len(v["paths"]) for k, v in planes.items()})
    for prog, by_name in sorted(scopes(planes, w0, w1).items()):
        print(prog)
        for name, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:25]:
            print(f"  {name:40s} {s:.6f}")
    print("idle phases:", idle_phases(devices, host))


if __name__ == "__main__":
    main(sys.argv[1])
