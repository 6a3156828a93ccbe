"""device step: mean ``tick_stage_scan_wait_s`` (device time the host did not
cover, plus the device-to-host fetch)."""


def read(r):
    return r.stage_ms("scan_wait")
