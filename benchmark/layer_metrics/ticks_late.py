"""tick: ticks that began more than half a period after they were due, all
nodes, over window and drain: the ``ticks_late`` counter."""

from benchmark.program_marks import counter_sum


def read(r):
    return counter_sum(r, "ticks_late")
