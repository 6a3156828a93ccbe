"""tick: what ``tick()`` does after ``tick_latency_s`` is observed (admission,
transactions, span harvest, hop fold, health) on the busiest node: the
``raft.tail`` span (``tick_stage_tail_s``)."""

from benchmark import stagespans


def read(r):
    s = stagespans.of(r)
    return None if s is None else s.mean_ms("tail")
