"""Kernels, `decode_attn`: the least bytes of the cache rows the traced
window's decode steps attend (`kv_rows_attended` x bytes a row holds over
all layers) over flash decode's device time (`attn_core`) x HBM
bandwidth, in %; None without the trace's scopes and counters."""
from benchlib.scopes import flash_decode_roofline


def read(ctx):
    return flash_decode_roofline(ctx)
