"""90th percentile of how late the load generator submitted each request
after its due time (ms, host clock), over the window's requests due clear
of the profiler's start (``trace.HOST_GUARD_S`` before it): the calls that
start and stop the profiler hold the host thread that submits."""
from chipbench.lib.bench import quantile


def read(r):
    lag = r.get("host.gen_lag_s")
    if not lag:
        return None
    return 1e3 * quantile(lag, 0.9)
