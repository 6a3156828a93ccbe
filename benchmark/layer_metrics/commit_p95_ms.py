"""client: the 95th percentile of the acknowledged writes of the traced run.  A tail here
sits on a tick boundary (latencies come in whole ticks) and flips by a tick
from run to run, so it is kept as a per-layer metric and not held to a bound."""


def read(r):
    if not r.commit_latencies_s:
        return None
    from benchmark.readings import percentile
    return 1e3 * percentile(r.commit_latencies_s, 95)
