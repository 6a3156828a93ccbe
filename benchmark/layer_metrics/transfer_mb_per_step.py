"""device step: megabytes a step moves between host and chip, both ways, mean
over the traced slice's steps, all nodes: the ``bytes`` statistic of the
``raft.dispatch_upload`` and ``raft.scan_fetch`` spans of the steps that have
both inside the slice (the packed buffers' sizes: every plane of the layouts
crosses every step, whatever moved in it).  A program whose spans carry no
``bytes`` (the parent of PR 34) yields nothing."""

from benchmark import spanstats

PHASES = ("dispatch_upload", "scan_fetch")


def read(r):
    stats = spanstats.of(r) or {}
    up, down = ({(node, tick): s["bytes"]
                 for node, ticks in stats.get(phase, {}).items()
                 for tick, s in ticks.items() if "bytes" in s}
                for phase in PHASES)
    whole = up.keys() & down.keys()
    if not whole:
        return None
    return sum(up[k] + down[k] for k in whole) / len(whole) / 1e6
