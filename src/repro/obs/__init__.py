"""Runtime observability: tracing spans, a metrics registry, model-vs-measured
kernel profiling, and the engine flight recorder (``docs/observability.md``).

The kill switch is the ``REPRO_OBS`` environment variable: unset or truthy →
enabled; ``0``/``off``/``no``/``false`` → the process-default tracer and
registry become no-op null backends (instrumented hot paths pay one empty
call per event).  The flight recorder is *not* gated — it is the black box a
postmortem needs precisely when nobody was watching, and its cost is one
bounded dict append per engine step.

Submodules: ``trace`` (spans, also written into the ``jax.profiler``
trace), ``metrics`` (registry + catalog), ``recorder`` (flight recorder),
``profiler`` (warmup+median kernel timing vs ``perf_model`` predictions),
``report`` (the attribution-table CLI: ``python -m repro.obs.report``).  ``profiler``/``report`` import the
fusion stack and are loaded lazily so that ``core``/``serve`` modules can
import ``repro.obs`` without cycles.
"""
from __future__ import annotations

import os

from repro.obs import metrics, recorder, trace
from repro.obs.metrics import (METRIC_CATALOG, NULL_REGISTRY, Registry,
                               default_registry, set_default_registry)
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import NULL_TRACER, Tracer, get_tracer, set_tracer

__all__ = [
    "enabled", "metrics", "trace", "recorder",
    "Registry", "NULL_REGISTRY", "default_registry", "set_default_registry",
    "METRIC_CATALOG",
    "Tracer", "NULL_TRACER", "get_tracer", "set_tracer",
    "FlightRecorder",
    "profiler",
]

_DISABLE_VALUES = ("0", "off", "no", "false")


def enabled() -> bool:
    """Observability master switch (``REPRO_OBS``).  Read when the
    process-default tracer/registry is first created; tests that flip the
    env also call ``set_tracer(None)`` / ``set_default_registry(None)`` to
    force re-evaluation."""
    return os.environ.get("REPRO_OBS", "1").strip().lower() \
        not in _DISABLE_VALUES


def __getattr__(name):
    # lazy: profiler imports repro.fusion, which (via core.tunecache) imports
    # repro.obs.metrics — eager import here would be a cycle
    if name == "profiler":
        import importlib

        module = importlib.import_module("repro.obs.profiler")
        globals()["profiler"] = module
        return module
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
