"""Record the small trace that ``test_trace.py`` reads: the tiny engine of
``tiny.py`` served on the chip under the harness's own annotations, a
dozen steps of it traced.

    python3 chipbench/tests/record_fixture.py <out.xplane.pb>

Run it from the root of a checkout on a machine with a TPU.  It prints
each plane and line of the trace with its first events, and the summary
``chipbench/lib/trace.py`` makes of it.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench.lib import bench, serve, traffic, weights
    from chipbench.lib import trace as trace_lib
    from chipbench.tests import tiny
    from repro.obs.trace import Tracer
    from repro.serve import Engine, EngineConfig

    bench.require_chips(1)
    spec, shape = tiny.TINY, tiny.TINY["engine"]
    cfg = serve.model_config(spec)
    eng = Engine(cfg, weights.make_params(cfg, 7),
                 EngineConfig(seed=7, **shape), tracer=Tracer())
    drv = serve.Driver(eng, traffic.generate(tiny.CLOSED, 7, 300),
                       tiny.CLOSED)
    drv.warm_up(eng.ecfg)
    for _ in range(12):
        drv.submit_next()
    for _ in range(4):
        drv.step()
    tmp = pathlib.Path(tempfile.mkdtemp())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # as ``trace_lib.Profile`` runs
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    with TraceAnnotation(trace_lib.WINDOW):
        for _ in range(12):
            for _uid in drv.step():
                drv.submit_next()
    jax.profiler.stop_trace()
    pb = sorted(tmp.rglob("*.xplane.pb"))[-1]
    shutil.copy(pb, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", line.name, len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    print(trace_lib.reduce_file(out))


if __name__ == "__main__":
    main(sys.argv[1])
