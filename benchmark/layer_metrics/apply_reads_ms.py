"""apply and reads: mean apply plus read-serving time of one tick."""


def read(r):
    return r.stage_ms("apply", "reads")
