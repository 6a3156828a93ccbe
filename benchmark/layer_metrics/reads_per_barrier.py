"""apply and reads: linearizable queries served per ReadIndex barrier, all
nodes, over the traced slice: the ``queries`` and ``barriers`` statistics of
the ``raft.reads`` spans.  1.0 = every read paid for a barrier of its own."""

from benchmark import spanstats


def read(r):
    served = spanstats.rows(spanstats.of(r), "reads", "barriers")
    barriers = sum(s["barriers"] for ticks in served.values() for s in ticks)
    if not barriers:
        return None
    return sum(s.get("queries", 0.0) for ticks in served.values()
               for s in ticks) / barriers
