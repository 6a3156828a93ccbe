"""inbox: acknowledgements (AppendEntries and InstallSnapshot replies) that an
inbox collapse overwrote, newest-wins per lane, a step: the ``merged``
statistic of the ``raft.dispatch_intake`` spans summed over the traced slice,
over its steps, all nodes.  Each is a slot of the sender's window that no
reply releases any more (``window_leaked_pct``).  0 where no source's queue
passed the collapse's backlog: no step outlasted three of its peers'.  A
program whose spans carry no such statistic (the parent of PR 37) yields
nothing."""

from benchmark import spanstats


def read(r):
    steps = [s["merged"] for ticks in spanstats.rows(
        spanstats.of(r), "dispatch_intake", "merged").values()
        for s in ticks]
    if not steps:
        return None
    return sum(steps) / len(steps)
