"""device step: open group lanes that are asleep (hibernated: the leader opens
no heartbeat round for them, the followers' election timers do not run) as a
share of a node's open lanes, mean over the traced slice's TIMER steps, on
the busiest node (the one whose ticks cost most): the ``asleep`` and ``open``
statistics of the ``raft.mirrors`` spans, which carry ``asleep`` on a timer
step alone.  0 wherever ``RaftConfig.hibernate_regions`` is off; near 100 on
a store whose traffic touches a few lanes in a hundred thousand.  A program
whose spans carry no ``asleep`` (the parent of PR 41) yields nothing."""

from benchmark import spanstats


def read(r):
    seen = spanstats.rows(spanstats.of(r), "mirrors", "asleep")
    if not seen:
        return None
    busiest = r.busiest if r.histograms and r.busiest in seen else max(seen)
    steps = [s for s in seen[busiest] if s.get("open")]
    if not steps:
        return None
    return 100.0 * sum(s["asleep"] / s["open"] for s in steps) / len(steps)
