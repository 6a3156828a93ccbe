"""device: share of the slice's device-idle time in which every node's tick
thread was inside ``raft.wait`` (the timer, not host work, kept the device
idle)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.idle_in_wait_pct()
