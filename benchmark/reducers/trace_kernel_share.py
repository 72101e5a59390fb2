"""Share of the device's busy time spent in Pallas kernels; only from a
profiler trace of a TPU."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * t["kernel_s"] / t["busy_s"] if t and t["busy_s"] else None
