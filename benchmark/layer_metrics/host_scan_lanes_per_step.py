"""tick: lanes the selection passes of a step's host phase ran over (the
masks, compares and sums by which persist, the rejection sweep, apply, reads
and maintain find their work), mean over the traced slice's steps, all
nodes: the ``scanned`` statistic of the ``raft.wal``, ``raft.apply``,
``raft.reads`` and ``raft.maintain`` spans of the steps that have all four
inside the slice.  The companion of ``host_lanes_per_step`` (lanes visited
one by one): a step that looks at every lane reads ``n_groups`` a pass, a
step worked from the rows that came down reads those rows.  A program whose
spans carry no ``scanned`` (the parent of PR 40) yields nothing."""

from benchmark import spanstats

PHASES = ("wal", "apply", "reads", "maintain")


def read(r):
    stats = spanstats.of(r) or {}
    by_phase = [{(node, tick): s["scanned"]
                 for node, ticks in stats.get(phase, {}).items()
                 for tick, s in ticks.items() if "scanned" in s}
                for phase in PHASES]
    whole = set.intersection(*(set(p) for p in by_phase))
    if not whole:
        return None
    return sum(p[k] for p in by_phase for k in whole) / len(whole)
