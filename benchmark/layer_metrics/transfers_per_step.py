"""device step: arrays a step moves between host and chip, both ways, mean
over the traced slice's steps, all nodes: the ``transfers`` statistic of the
``raft.dispatch_upload`` and ``raft.scan_fetch`` spans of the steps that
have both inside the slice (one packed word buffer and one flag buffer each
way up to ``CHUNK_BYTES`` of planes: 4; more where a layout closes a buffer
and opens the next).  A program whose spans carry no ``transfers`` (the
parent of PR 27) yields nothing."""

from benchmark import spanstats

PHASES = ("dispatch_upload", "scan_fetch")


def read(r):
    stats = spanstats.of(r) or {}
    up, down = ({(node, tick): s["transfers"]
                 for node, ticks in stats.get(phase, {}).items()
                 for tick, s in ticks.items() if "transfers" in s}
                for phase in PHASES)
    whole = up.keys() & down.keys()
    if not whole:
        return None
    return sum(up[k] + down[k] for k in whole) / len(whole)
