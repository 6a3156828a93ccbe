"""apply and reads: the share of the linearizable queries served in the traced
slice that the lease released in the step that stamped them on evidence of an
EARLIER tick (the lease outlived the period its acknowledgements arrived in:
``rafting_tpu/core/step.py`` phase 6b), all nodes: the ``lease_carried`` and
``queries`` statistics of the ``raft.reads`` spans.  Never more than
``lease_read_share``; 0 wherever every lane hears a heartbeat round every
tick.  A program whose spans carry no ``lease_carried`` (the parent of PR 39)
yields nothing."""

from benchmark import spanstats


def read(r):
    served = spanstats.rows(spanstats.of(r), "reads", "lease_carried")
    queries = sum(s.get("queries", 0.0) for ticks in served.values()
                  for s in ticks)
    if not queries:
        return None
    return sum(s["lease_carried"] for ticks in served.values()
               for s in ticks) / queries
