"""inbox: how long a peer's slice stood in the ``InboxAccumulator`` before a
tick popped it: mean ``inbox_wait_s`` on the node where it is longest."""

from benchmark.program_marks import worst_node_mean


def read(r):
    v = worst_node_mean(r, "inbox_wait_s")
    return None if v is None else 1e3 * v
