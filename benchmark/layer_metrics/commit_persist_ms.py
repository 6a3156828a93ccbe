"""persist: a sampled write from the offer to its fsync barrier: mean
``lat_offer_stage_s`` + ``lat_stage_fsync_s`` over the same spans."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_offer_stage_s", "lat_stage_fsync_s")
