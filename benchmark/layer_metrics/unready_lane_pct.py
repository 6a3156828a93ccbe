"""device step: open lanes a node leads without being ready on them (a
majority of the followers' windows cooling down after an RPC timeout, or a
leader still waiting for its first majority), as a share of the lanes it
leads, mean over the traced slice's steps, all nodes that lead: the
``unready`` and ``led`` statistics of the ``raft.mirrors`` spans.  An
operation on such a lane is refused with ``NotReadyError``.  0 in an
undisturbed slice; it is the part of ``leaderless_pct`` that is not an
election.  A program whose spans carry no such statistic (the parent of PR
37) yields nothing."""

from benchmark import spanstats


def read(r):
    seen = spanstats.rows(spanstats.of(r), "mirrors", "unready")
    if not seen:
        return None
    led = [s for steps in seen.values() for s in steps if s.get("led")]
    if not led:
        return 0.0
    return 100.0 * sum(s["unready"] / s["led"] for s in led) / len(led)
