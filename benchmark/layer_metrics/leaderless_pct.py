"""device step: open group lanes for which a node neither leads ready nor
knows a leader, as a share of its open lanes, mean over the traced slice's
steps, all nodes: the ``leaderless`` and ``open`` statistics of the
``raft.mirrors`` spans (sampled where a step's roles reach the host).  0 in
a window in which no election ran.  A program whose spans carry no such
statistic (the parent of PR 31) yields nothing."""

from benchmark import spanstats


def read(r):
    seen = [s for ticks in spanstats.rows(
        spanstats.of(r), "mirrors", "leaderless").values() for s in ticks
        if s.get("open")]
    if not seen:
        return None
    return 100.0 * sum(s["leaderless"] / s["open"] for s in seen) / len(seen)
