"""Device: 1 - (union of device operation intervals) / traced window, from
the profiler trace, in %."""
from benchlib.readers import device_idle


def read(ctx):
    return device_idle(ctx)
