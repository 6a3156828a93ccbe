"""device step: the device-to-host copy of the step's results (``device_get``
after the wait) on the busiest node: the ``raft.scan_fetch`` span
(``tick_stage_scan_fetch_s``)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.mean_ms("scan_fetch")
