"""apply and reads: the share of the linearizable queries served in the traced
slice whose batch was stamped in a step that woke its lane from hibernation,
all nodes: such a read finds no lease evidence (a leader drops it when it
falls asleep: ``rafting_tpu/core/step.py`` "hibernation", case b) and waits
for the barrier heartbeat that wakes its followers and for their
acknowledgements; the ``woke`` and ``queries`` statistics of the
``raft.reads`` spans.  ``woke + lease_hits <= queries``.  0 wherever
``RaftConfig.hibernate_regions`` is off.  A program whose spans carry no
``woke`` (the parent of PR 41), or a slice that served no query, yields
nothing."""

from benchmark import spanstats


def read(r):
    served = spanstats.rows(spanstats.of(r), "reads", "woke")
    queries = sum(s.get("queries", 0.0) for ticks in served.values()
                  for s in ticks)
    if not queries:
        return None
    return sum(s["woke"] for ticks in served.values()
               for s in ticks) / queries
