"""tick: ``_dispatch`` per tick on the busiest node: the ``raft.dispatch_intake``,
``raft.dispatch_upload`` and ``raft.dispatch_enqueue`` spans of the traced slice
(the three parts of ``tick_stage_dispatch_s``)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.mean_ms(
        "dispatch_intake", "dispatch_upload", "dispatch_enqueue")
