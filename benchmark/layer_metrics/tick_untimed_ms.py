"""tick: the part of a tick no stage timer covers (today ``_dispatch`` and
the uploads): ``tick_latency_s`` minus the seven stage means."""

from benchmark.readings import STAGES, TICK


def read(r):
    work, stages = r.mean_ms(TICK), r.stage_ms(*STAGES)
    return None if work is None or stages is None else work - stages
