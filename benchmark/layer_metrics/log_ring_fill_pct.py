"""maintain: how full the fullest log ring stands, as a share of its slots,
mean over the traced slice's ticks on the node that leads most lanes: the
``ring_used``, ``ring_slots`` and ``led`` statistics of the ``raft.maintain``
spans.  At 100 the ring refuses intake."""

from benchmark import spanstats


def read(r):
    seen = spanstats.rows(spanstats.of(r), "maintain", "ring_slots")
    if not seen:
        return None
    lead = max(seen, key=lambda n: sum(s.get("led", 0.0) for s in seen[n]))
    return 100.0 * sum(s["ring_used"] / s["ring_slots"]
                       for s in seen[lead]) / len(seen[lead])
