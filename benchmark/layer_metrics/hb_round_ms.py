"""inbox: the heartbeat round's length: from the start of the timer's step
that sent a period's heartbeats to the end of the first step whose drained
slices held acknowledgements of that period from every peer the node leads
lanes against, mean over the traced slice's periods, the node where it is
longest: the ``hb_round_s`` statistic the program writes on the ``raft.tail``
span of the step that closes a round.  Over ``tick_ms`` it is the share of a
period in which a read cannot ride the lease.  A program whose spans carry no
``hb_round_s`` (the parent of PR 34), or a slice in which no node that leads
closed a round, yields nothing."""

from benchmark import spanstats


def read(r):
    closed = spanstats.rows(spanstats.of(r), "tail", "hb_round_s")
    means = [sum(s["hb_round_s"] for s in steps) / len(steps)
             for steps in closed.values()]
    return 1e3 * max(means) if means else None
