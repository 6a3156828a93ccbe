"""tick: group lanes the host phase of a step walked in Python (staged to the
WAL, packed for a peer, applied, served a read, checkpointed or compacted),
mean over the traced slice's steps, all nodes: the ``lanes`` statistic of the
``raft.wal``, ``raft.send``, ``raft.apply``, ``raft.reads`` and
``raft.maintain`` spans.  What a step walks should follow what moved in it,
not how many lanes the node holds.  A program whose spans carry no ``lanes``
(the parent of PR 31) yields nothing."""

from benchmark import spanstats

PHASES = ("wal", "send", "apply", "reads", "maintain")


def read(r):
    stats = spanstats.of(r)
    steps = {}
    for phase in PHASES:
        for node, ticks in (stats or {}).get(phase, {}).items():
            for tick, s in ticks.items():
                if "lanes" in s:
                    steps[node, tick] = steps.get((node, tick), 0.0) \
                        + s["lanes"]
    if not steps:
        return None
    return sum(steps.values()) / len(steps)
