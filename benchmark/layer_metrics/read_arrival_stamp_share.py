"""apply and reads: the share of the read batches stamped in the traced slice
that were stamped in a step the node's loop started for arriving work, not in
its timer's step, all nodes: the ``arrival_stamps`` statistic over the
``stamps`` statistic of the ``raft.reads`` spans.  1 = no read waited for the
period's timer to be stamped; 0 = every one did (a program that stamps strict
reads at the timer alone).  A program whose spans carry no ``stamps`` (the
parent of PR 45), or a slice that stamped no batch, yields nothing."""

from benchmark import spanstats


def read(r):
    steps = [s for ticks in spanstats.rows(
        spanstats.of(r), "reads", "stamps").values() for s in ticks]
    stamps = sum(s["stamps"] for s in steps)
    if not stamps:
        return None
    return sum(s.get("arrival_stamps", 0.0) for s in steps) / stamps
