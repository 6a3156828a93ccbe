"""tick: every host-to-device upload of one tick (``HostInbox`` and ``Messages``)
on the busiest node: the ``raft.dispatch_upload`` span
(``tick_stage_dispatch_upload_s``)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.mean_ms("dispatch_upload")
