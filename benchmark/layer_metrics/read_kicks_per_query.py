"""apply and reads: barrier heartbeats asked for per linearizable query served
in the traced slice, all nodes: a read batch that the step which stamped it
could not release (no lease evidence within reach) is left pending and kicks a
heartbeat of its own for its lane, a round trip that the query waits for: the
``kicks`` statistic of the ``raft.reads`` spans of the steps that stamped a
batch, over the ``queries`` of the steps that served one.  A program whose
spans carry no ``kicks`` (the parent of PR 39), or a slice that served no
query, yields nothing."""

from benchmark import spanstats


def read(r):
    stats = spanstats.of(r)
    stamped = spanstats.rows(stats, "reads", "kicks")
    queries = sum(s["queries"] for ticks in
                  spanstats.rows(stats, "reads", "queries").values()
                  for s in ticks)
    if not stamped or not queries:
        return None
    return sum(s["kicks"] for ticks in stamped.values()
               for s in ticks) / queries
