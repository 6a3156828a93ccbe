"""kernels: the step program against the memory roofline (it is bytes-bound:
no matrix multiplication): least time = bytes the step must move over the
peak bytes/s of the device, over the device time of one execution."""

from benchmark.stepbytes import roofline_share_pct


def read(r):
    s = r.step_device_s
    if s is None or not r.step_bytes or not r.peak_bytes_per_s:
        return None
    return roofline_share_pct(r.step_bytes, s, r.peak_bytes_per_s)
