"""A test architecture, `window_gqa`: InternLM2's block with sliding-window
attention, each query attending its `sliding_window` latest keys. The
program serves it from a ring arena of min(rows, window) rows a slot.
Everything but the window is InternLM2's, taken from its module, which
this one reads and does not change."""
from __future__ import annotations

import dataclasses
import os

from benchlib import flops, harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
base = harness.load_module(_ROOT, "arch", "internlm2")

served_widths = base.served_widths
slim_plan = base.slim_plan
make_params = base.make_params
kv_row_bytes = base.kv_row_bytes
weight_bytes = base.weight_bytes


def _window(cfg: dict) -> int:
    return int(cfg["sliding_window"])


def program_config(cfg: dict):
    return dataclasses.replace(base.program_config(cfg), window=_window(cfg))


def slot_bytes(cfg: dict, rows: int) -> int:
    """A slot's ring holds min(rows, window) rows of every layer."""
    return min(rows, _window(cfg)) * kv_row_bytes(cfg)


def decode_flops(ctx: dict) -> float:
    cs = flops.context_sum(ctx["requests"], _window(ctx["config"]))
    return base.count_flops(ctx["widths"], ctx["stats"]["decode_tokens"], cs)


def decode_bytes(ctx: dict) -> float:
    cfg = ctx["config"]
    cs = flops.context_sum(ctx["requests"], _window(cfg))
    return base.count_bytes(ctx["widths"], ctx["stats"]["decode_steps"], cs,
                            kv_row_bytes(cfg))
