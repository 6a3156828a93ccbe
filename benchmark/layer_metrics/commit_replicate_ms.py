"""inbox: a sampled write from its fsync to the commit: mean
``lat_fsync_send_s`` + ``lat_send_commit_s`` over the same spans: the round
trip through the followers, two more ticks for each slice standing in the
leader's inbox."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_fsync_send_s", "lat_send_commit_s")
