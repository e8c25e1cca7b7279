"""Shared arithmetic of the metric readers in `bench/metrics/`. A reader
returns None where its cell gives it nothing to read; it never stands a
0 in for a share it could not measure. What depends on the architecture
(operations and bytes) comes from its module, `ctx["arch"]`."""
from __future__ import annotations


def decode_mfu(ctx) -> float | None:
    """Model FLOPs of the window's decode tokens (the architecture
    module's count) over decode time x peak."""
    st, peaks = ctx["stats"], ctx["peaks"]
    if peaks is None or st["decode_s"] <= 0:
        return None
    f = ctx["arch"].decode_flops(ctx)
    return 100.0 * f / (st["decode_s"] * peaks["bf16_flops_per_s"])


def decode_roofline(ctx) -> float | None:
    """Least time the decode steps could take on this chip (the larger of
    FLOPs over peak and bytes over HBM bandwidth, as the architecture
    module counts them) over their time."""
    st, peaks = ctx["stats"], ctx["peaks"]
    if peaks is None or st["decode_s"] <= 0:
        return None
    t_flops = ctx["arch"].decode_flops(ctx) / peaks["bf16_flops_per_s"]
    t_bytes = ctx["arch"].decode_bytes(ctx) / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / st["decode_s"]


def device_idle(ctx) -> float | None:
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
