"""apply and reads: the share of the linearizable queries served in the traced
slice that the lease released in the step that stamped them (no ReadIndex
round trip), all nodes: the ``lease_hits`` and ``queries`` statistics of the
``raft.reads`` spans.  A program whose spans carry no ``lease_hits`` (the
parent of PR 31) yields nothing."""

from benchmark import spanstats


def read(r):
    served = spanstats.rows(spanstats.of(r), "reads", "lease_hits")
    queries = sum(s.get("queries", 0.0) for ticks in served.values()
                  for s in ticks)
    if not queries:
        return None
    return sum(s["lease_hits"] for ticks in served.values()
               for s in ticks) / queries
