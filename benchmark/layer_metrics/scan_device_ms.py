"""device step: the wait for the device to finish this tick's step
(``block_until_ready``) on the busiest node: the ``raft.scan_device`` span
(``tick_stage_scan_device_s``)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.mean_ms("scan_device")
