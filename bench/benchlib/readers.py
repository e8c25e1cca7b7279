"""Shared arithmetic of the metric readers in `bench/metrics/`. A reader
returns None where its cell gives it nothing to read; it never stands a
0 in for a share it could not measure."""
from __future__ import annotations

from benchlib import flops


def context_sum(ctx) -> int:
    return sum(flops.decode_context_sum(r["prompt"].size, len(r["tokens"]))
               for r in ctx["requests"] if len(r["tokens"]) > 0)


def decode_mfu(ctx) -> float | None:
    """Model FLOPs of the window's decode tokens over decode time x peak."""
    st, peaks = ctx["stats"], ctx["peaks"]
    if peaks is None or st["decode_s"] <= 0:
        return None
    f = flops.decode_flops(ctx["widths"], st["decode_tokens"],
                           context_sum(ctx))
    return 100.0 * f / (st["decode_s"] * peaks["bf16_flops_per_s"])


def decode_roofline(ctx) -> float | None:
    """Least time the decode steps could take on this chip (the larger of
    FLOPs over peak and bytes over HBM bandwidth) over their time."""
    st, peaks = ctx["stats"], ctx["peaks"]
    if peaks is None or st["decode_s"] <= 0:
        return None
    cs = context_sum(ctx)
    t_flops = flops.decode_flops(ctx["widths"], st["decode_tokens"], cs) \
        / peaks["bf16_flops_per_s"]
    t_bytes = flops.decode_bytes(ctx["widths"], st["decode_steps"], cs,
                                 ctx["kv_row_bytes"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / st["decode_s"]


def device_idle(ctx) -> float | None:
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
