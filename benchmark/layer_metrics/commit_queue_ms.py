"""client: a sampled write from ``submit`` to the tick whose step is offered it:
mean ``lat_submit_offer_s`` over the spans of the window, all nodes."""

from benchmark.program_marks import pooled_mean_ms


def read(r):
    return pooled_mean_ms(r, "lat_submit_offer_s")
