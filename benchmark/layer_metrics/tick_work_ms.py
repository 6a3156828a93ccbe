"""tick: mean work of one tick (``tick_latency_s``) on the busiest node."""

from benchmark.readings import TICK


def read(r):
    return r.mean_ms(TICK)
