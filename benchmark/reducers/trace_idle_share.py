"""Share of the traced window in which no operation ran on the device; only
from a profiler trace of a TPU."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t and t["window_s"] else None
