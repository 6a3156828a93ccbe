"""tick: the gap the traced slice's arrival steps were given, mean, all
nodes, in ms: how long after the step before had ended ``arrival_step_at``
let the next one start at the earliest, the ``gap_ms`` statistic of the
``raft.dispatch_intake`` spans whose ``arrival`` is 1.  Since PR 43 the gap is
the time the step before held the interpreter (its duration less its waits for
the device, the copy down and the WAL's fsync, ``waited_ms`` on the same
span); before, the step's whole duration, so against ``tick_work_ms`` it read
1.0 by construction.  A program whose spans carry no such statistic (the
parent of PR 43), or a slice without an arrival step, yields nothing."""

from benchmark import spanstats


def read(r):
    gaps = [s["gap_ms"] for ticks in spanstats.rows(
        spanstats.of(r), "dispatch_intake", "gap_ms").values()
        for s in ticks if s.get("arrival")]
    if not gaps:
        return None
    return sum(gaps) / len(gaps)
