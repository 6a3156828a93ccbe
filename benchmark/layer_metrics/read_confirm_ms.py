"""apply and reads: a sampled read from its offer to the served reply (stamp,
leadership evidence, apply frontier): mean ``lat_read_confirm_s``."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_read_confirm_s")
