"""kernels: device time of one execution of the step program, from the trace."""


def read(r):
    s = r.step_device_s
    return None if s is None else 1e3 * s
