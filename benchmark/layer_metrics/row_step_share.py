"""device step: the share of the traced slice's steps whose ``[G]`` planes
crossed between host and chip as rows in BOTH directions, all nodes: the
``planes_dense`` statistic (0: HostInbox went up, or the Readback came down,
as the rows of the lanes that had something to say or moved; 1: as whole
planes) of the ``raft.dispatch_upload`` and ``raft.scan_fetch`` spans of the
steps that have both inside the slice.  0.0 on a shape that keeps the dense
program (its spans say ``planes_dense`` 1 every step); a step of a large node
goes whole when more lanes have something to say or moved than the row
buffers hold (an election storm, the step after a purge, a node's first).  A
program whose spans carry no ``planes_dense`` (the parent of PR 38) yields
nothing."""

from benchmark import spanstats

PHASES = ("dispatch_upload", "scan_fetch")


def read(r):
    stats = spanstats.of(r) or {}
    up, down = ({(node, tick): s["planes_dense"]
                 for node, ticks in stats.get(phase, {}).items()
                 for tick, s in ticks.items() if "planes_dense" in s}
                for phase in PHASES)
    whole = up.keys() & down.keys()
    if not whole:
        return None
    return sum(not up[k] and not down[k] for k in whole) / len(whole)
