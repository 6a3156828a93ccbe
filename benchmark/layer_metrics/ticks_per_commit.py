"""client: the median commit latency in units of the measured tick period."""


def read(r):
    if not r.commit_latencies_s or not r.tick_period_s:
        return None
    from benchmark.readings import percentile
    return percentile(r.commit_latencies_s, 50) / r.tick_period_s
