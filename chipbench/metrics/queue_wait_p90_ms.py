"""90th percentile of the wait from each request's due time to the start
of its ``engine.prefill`` span (ms, host clock), over the window's requests
due clear of the profiler's start (``trace.HOST_GUARD_S`` before it); one
never prefilled waits for ever."""
from chipbench.lib.bench import quantile


def read(r):
    wait = r.get("host.queue_wait_s")
    if not wait:
        return None
    return 1e3 * quantile(wait, 0.9)
