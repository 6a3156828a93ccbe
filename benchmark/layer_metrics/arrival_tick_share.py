"""tick: the share of the traced slice's steps that a node's loop started for
arriving work (a peer's slice, a write, a read) and not for its timer, all
nodes: the ``arrival`` statistic (0 or 1) of the ``raft.dispatch_intake``
spans.  0 = every step waited for the period's timer; a program whose spans
carry no such statistic (the parent of PR 29) yields nothing."""

from benchmark import spanstats


def read(r):
    steps = [s["arrival"] for ticks in spanstats.rows(
        spanstats.of(r), "dispatch_intake", "arrival").values()
        for s in ticks]
    if not steps:
        return None
    return sum(steps) / len(steps)
