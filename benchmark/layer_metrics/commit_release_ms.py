"""apply and reads: a sampled write from commit to the acknowledged future: mean
``lat_commit_apply_s`` + ``lat_apply_ack_s`` over the same spans."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_commit_apply_s", "lat_apply_ack_s")
