"""tick: the share of the traced slice's arrival steps (steps a node's loop
started for waiting work and not for its timer) that started at the END OF
THE GAP ``arrival_step_at`` leaves behind the step before, and not at the
work's arrival: the work stood waiting while the gap ran out.  All nodes: the
``held`` statistic (0 or 1) of the ``raft.dispatch_intake`` spans whose
``arrival`` is 1.  High = the loops are limited by the gap, and a shorter gap
is a shorter wait at every hop; low = steps start when work arrives and the
gap costs nothing.  A program whose spans carry no such statistic (the parent
of PR 43), or a slice without an arrival step, yields nothing."""

from benchmark import spanstats


def read(r):
    steps = [s["held"] for ticks in spanstats.rows(
        spanstats.of(r), "dispatch_intake", "held").values()
        for s in ticks if s.get("arrival")]
    if not steps:
        return None
    return sum(steps) / len(steps)
