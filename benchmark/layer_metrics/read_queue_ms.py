"""apply and reads: a sampled read's wait for its group's one offer slot
(submitted -> offered): mean ``lat_read_queue_s``, all nodes."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_read_queue_s")
