"""tick: the time inside ``tick()`` (first phase start to the end of the tail)
that no stage span covers, per tick on the busiest node: the check that the
stages cover the tick."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.unspanned_ms()
