"""Kernels, `gemm_core` epilogues / XLA dots: the served weight bytes
(every projection and the head, once a step) x the traced window's decode
steps over the device time under `proj` in the decode window x HBM
bandwidth, in %; None without the trace's scopes and counters."""
from benchlib.scopes import proj_roofline


def read(ctx):
    return proj_roofline(ctx)
