"""Model step: the least time the window's decode steps could take on this
chip (the larger of FLOPs / bf16 peak and bytes / HBM bandwidth; bytes =
every served weight once a step and each attended cache row) over the
engine's decode time, in %."""
from benchlib.readers import decode_roofline


def read(ctx):
    return decode_roofline(ctx)
