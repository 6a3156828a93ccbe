"""device step: the share of the traced slice's steps whose messages crossed
between host and chip as columns in BOTH directions, all nodes: the ``dense``
statistic (0: columns, 1: the dense planes) of the ``raft.dispatch_upload``
and ``raft.scan_fetch`` spans of the steps that have both inside the slice.
0.0 on a shape that keeps the dense program (its spans say ``dense`` 1 every
step); a step of a large node goes dense when a source's or a row's messages
do not fit the column buffers (a heartbeat round, an election).  A program
whose spans carry no ``dense`` (the parent of PR 35) yields nothing."""

from benchmark import spanstats

PHASES = ("dispatch_upload", "scan_fetch")


def read(r):
    stats = spanstats.of(r) or {}
    up, down = ({(node, tick): s["dense"]
                 for node, ticks in stats.get(phase, {}).items()
                 for tick, s in ticks.items() if "dense" in s}
                for phase in PHASES)
    whole = up.keys() & down.keys()
    if not whole:
        return None
    return sum(not up[k] and not down[k] for k in whole) / len(whole)
