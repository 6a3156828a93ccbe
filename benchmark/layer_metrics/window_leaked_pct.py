"""device step: the share of a leader's window slots that stand occupied for
good: per node, the MINIMUM over the traced slice's steps of ``win_occupied``
over ``win_slots`` on the ``raft.mirrors`` spans (the slots un-acknowledged
sends hold over the (lane, follower) pairs the node leads, of
``inflight_limit`` a pair, summed on the device in the step's readback);
the worst node, in %.  Between two heartbeat rounds nothing legitimate is in
flight but a write or two, so what the minimum still holds is what no reply
will ever release: the slots of replies an inbox collapse merged away.  0
where nothing leaked; at 100 every window is full and every lane a timeout
from its cool-down.  A node that leads nothing has nothing to leak.  A
program whose spans carry no such statistic (the parent of PR 37) yields
nothing."""

from benchmark import spanstats


def read(r):
    seen = spanstats.rows(spanstats.of(r), "mirrors", "win_slots")
    if not seen:
        return None
    return 100.0 * max(
        min(s["win_occupied"] / s["win_slots"] if s["win_slots"] else 0.0
            for s in steps)
        for steps in seen.values())
