"""apply and reads: the mean ReadIndex round in the traced slice, all nodes,
in ms: from the instant the host phase of the step that stamped a read batch
saw the stamp to the instant the host phase of the step whose acknowledgements
released it saw the release, on the node's own monotonic clock, over the
batches that a LATER step than the stamping one released (a batch the lease
releases in its stamping step pays no round and is not counted): the
``round_ms`` statistic over the ``rounds`` statistic of the ``raft.reads``
spans.  A program whose spans carry neither (the parent of PR 45), or a slice
in which no batch paid a round, yields nothing."""

from benchmark import spanstats


def read(r):
    steps = [s for ticks in spanstats.rows(
        spanstats.of(r), "reads", "rounds").values() for s in ticks]
    rounds = sum(s["rounds"] for s in steps)
    if not rounds:
        return None
    return sum(s.get("round_ms", 0.0) for s in steps) / rounds
