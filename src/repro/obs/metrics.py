"""Metrics registry: counters, gauges, histograms — the measurement substrate
the serving engine, the fusion compiler and the tune cache publish into.

Two backends share one interface:

* :class:`Registry` — real instruments behind a lock, snapshot-exportable as
  plain JSON (``snapshot()``).  Benchmarks consume snapshots instead of
  hand-rolled dicts (``BENCH_serve.json``), and ``repro.obs.report`` prints
  the tune-cache section from the process-global default.
* :class:`NullRegistry` — every instrument is a shared no-op singleton whose
  methods are empty.  When observability is disabled (``REPRO_OBS=0``) the
  instrumented hot paths pay one attribute load + one empty call per event,
  which is within noise of the uninstrumented code (pinned by the
  null-backend smoke test).

Ownership: code with a natural owner (one :class:`~repro.serve.engine.Engine`)
gets its *own* ``Registry`` so two engines in one process never mix counts;
code without one (``core.tunecache``, ``fusion.lowering``) publishes to the
process-global :func:`default_registry`.

Metric *names* are a stable, append-only catalog (:data:`METRIC_CATALOG`,
documented in ``docs/observability.md`` — same contract as the TPPxxx
diagnostic codes): dashboards and CI gates key on them, so a name is never
renamed or repurposed, only added.  ``Registry`` accepts unknown names (user
code may add its own) but the catalog test pins every name this repo emits.
"""
from __future__ import annotations

import math
import threading
from typing import Optional

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "NullRegistry",
    "NULL_REGISTRY", "default_registry", "set_default_registry",
    "METRIC_CATALOG",
]


# -- the append-only name catalog (see docs/observability.md) ---------------

METRIC_CATALOG = {
    # serving engine (per-Engine registry)
    "serve.requests.submitted": "counter: requests accepted by Engine.submit",
    "serve.requests.finished": "counter: requests retired FINISHED",
    "serve.requests.failed": "counter: requests retired FAILED (incl. NaN quarantine)",
    "serve.requests.cancelled": "counter: requests retired CANCELLED",
    "serve.requests.timed_out": "counter: requests retired TIMED_OUT",
    "serve.tokens": "counter: generated tokens harvested to the host",
    "serve.preemptions": "counter: memory-pressure / fault-injected preemptions",
    "serve.page_grows": "counter: pages appended to running slots (optimistic mode)",
    "serve.flight_dumps": "counter: flight-recorder fault dumps taken",
    "serve.queue_depth": "gauge: waiting requests (PREEMPTED requeues included)",
    "serve.slots.active": "gauge: slots holding a running request",
    "serve.pages.used": "gauge: pages owned by running slots",
    "serve.pages.total": "gauge: page-pool size (constant per engine)",
    "serve.step_s": "histogram: Engine.step wall time, seconds",
    "serve.decode_iterations": "counter: iterations the decode segments' while_loops ran",
    "serve.decode_slot_iterations": "counter: active slots summed over those iterations",
    "serve.decode_kv_pages": "counter: KV pages the active slots' paged attention read, summed over those iterations",
    "serve.moe_assignments": "counter: the active slots' token-expert assignments on held experts, summed over MoE layers and decode iterations",
    "serve.moe_experts_touched": "counter: held experts with an active slot's token, summed over MoE layers and decode iterations",
    # fusion compiler (process-global registry)
    "fusion.compile_cache.hits": "counter: compile_for_backend memo hits",
    "fusion.compile_cache.misses": "counter: compile_for_backend memo misses",
    "fusion.lowerings": "counter: fused Pallas nests planned (per new shape — recompiles)",
    "fusion.fallbacks": "counter: graphs degraded to the composed-TPP XLA reference",
    # autotuner / persistent tune cache (process-global registry)
    "tune.searches": "counter: autotune_with_stats invocations that ran a search",
    "tune.cache.hits": "counter: persistent tune-cache lookups served from disk",
    "tune.cache.misses": "counter: persistent tune-cache lookups that missed",
    "tune.cache.corrupt_recoveries": "counter: corrupted entries discarded + re-tuned",
    "tune.cache.store_failures": "counter: entries that could not be persisted",
}


# -- instruments ------------------------------------------------------------

class Counter:
    """Monotone accumulator.  ``inc`` is the whole API."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins instantaneous value (queue depth, pool occupancy)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Streaming histogram over fixed bucket upper bounds (seconds-scale
    defaults suit latency).  Keeps count/sum/min/max plus per-bucket counts —
    enough for p50/p99 estimates in snapshots without storing observations."""

    __slots__ = ("name", "bounds", "_counts", "_n", "_sum", "_min", "_max",
                 "_lock")

    DEFAULT_BOUNDS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0,
                      3.0, 10.0)

    def __init__(self, name: str, bounds: Optional[tuple] = None):
        self.name = name
        self.bounds = tuple(bounds) if bounds is not None else \
            self.DEFAULT_BOUNDS
        self._counts = [0] * (len(self.bounds) + 1)   # + overflow bucket
        self._n = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        i = 0
        for b in self.bounds:
            if v <= b:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._n += 1
            self._sum += v
            self._min = min(self._min, v)
            self._max = max(self._max, v)

    @property
    def count(self) -> int:
        return self._n

    def quantile(self, q: float) -> float:
        """Bucket-boundary quantile estimate (exact only at boundaries)."""
        if not self._n:
            return 0.0
        rank = q * self._n
        seen = 0
        for i, c in enumerate(self._counts):
            seen += c
            if seen >= rank and c:
                return self.bounds[i] if i < len(self.bounds) else self._max
        return self._max

    def summary(self) -> dict:
        return {
            "count": self._n,
            "sum": self._sum,
            "min": self._min if self._n else None,
            "max": self._max if self._n else None,
            "mean": (self._sum / self._n) if self._n else None,
            "p50": self.quantile(0.5),
            "p99": self.quantile(0.99),
            "bounds": list(self.bounds),
            "bucket_counts": list(self._counts),
        }


# -- registries -------------------------------------------------------------

class Registry:
    """Get-or-create instrument store.  Asking twice for one name returns the
    same object; asking for one name as two different kinds raises."""

    def __init__(self):
        self._instruments: dict[str, object] = {}
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return True

    def _get(self, name: str, cls, *args):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, *args)
                self._instruments[name] = inst
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}")
            return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, bounds: Optional[tuple] = None) -> Histogram:
        return self._get(name, Histogram, bounds)

    def names(self) -> list[str]:
        return sorted(self._instruments)

    def snapshot(self) -> dict:
        """JSON-serializable {name: value-or-summary}: counters → int,
        gauges → float, histograms → summary dict."""
        out = {}
        with self._lock:
            items = list(self._instruments.items())
        for name, inst in items:
            if isinstance(inst, Counter):
                out[name] = inst.value
            elif isinstance(inst, Gauge):
                out[name] = inst.value
            else:
                out[name] = inst.summary()
        return out


class _NullInstrument:
    """One object, every instrument kind, every method a no-op."""

    __slots__ = ()
    name = "<null>"
    value = 0
    count = 0

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def quantile(self, q: float) -> float:
        return 0.0

    def summary(self) -> dict:
        return {}


_NULL_INSTRUMENT = _NullInstrument()


class NullRegistry:
    """The disabled backend: hands out the shared no-op instrument."""

    @property
    def enabled(self) -> bool:
        return False

    def counter(self, name: str) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def gauge(self, name: str) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def histogram(self, name: str,
                  bounds: Optional[tuple] = None) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def names(self) -> list[str]:
        return []

    def snapshot(self) -> dict:
        return {}


NULL_REGISTRY = NullRegistry()

_default_lock = threading.Lock()
_default: "Registry | NullRegistry | None" = None


def default_registry():
    """The process-global registry: a real :class:`Registry` when
    observability is enabled (``REPRO_OBS`` unset or truthy), the shared
    :data:`NULL_REGISTRY` otherwise.  Owner-less publishers (tune cache,
    fusion compiler) write here; the serving engine owns its own."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                from repro.obs import enabled
                _default = Registry() if enabled() else NULL_REGISTRY
    return _default


def set_default_registry(registry) -> "Registry | NullRegistry | None":
    """Swap the process-global registry (tests; a fresh one isolates counts).
    Returns the previous value — ``None`` means it had never been created."""
    global _default
    with _default_lock:
        prev = _default
        _default = registry
    return prev
