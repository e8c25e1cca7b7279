"""Model step / KV arena: the share of the decode window's device time
in the cache row writes (`kv_write`) and in the ops of the decode step
that carry no part scope (the slices, updates, reshapes and copies of the
arena that XLA adds), in %, from the profiler trace's scopes
(`benchlib.scopes`); None without them."""
from benchlib.scopes import kv_move_share


def read(ctx):
    return kv_move_share(ctx)
