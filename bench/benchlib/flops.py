"""Operations and bytes the served model needs, counted from the served
widths (`weights.served_widths`). Model FLOPs only: the embedding lookup
is a gather and counts nothing, idle slots count nothing, and nothing
recomputed counts. Bytes are the least a step has to move: every weight
once per decode step, and the cached K/V rows each active request
attends to.
"""
from __future__ import annotations

from benchlib.weights import projection_shapes


def matmul_params(w: dict) -> dict:
    """Weights that multiply each token: per layer (all layers), and the
    head."""
    shp = projection_shapes(w)
    per_layer = sum(k * n for name, (k, n) in shp.items() if name != "head")
    return {"layers": w["L"] * per_layer, "head": shp["head"][0] * shp["head"][1]}


def weight_bytes(w: dict) -> int:
    """Bytes one decode step reads from the served weights (the embedding
    rows it gathers are left out)."""
    mp = matmul_params(w)
    n = mp["layers"] + mp["head"]
    if w["bits"] is None:
        return 2 * n
    return n * w["bits"] // 8


def decode_context_sum(prompt_len: int, out_len: int) -> int:
    """Keys attended over a request's decode steps: the first output
    token comes from the prefill, step i (1..out_len-1) attends the
    prompt and i tokens."""
    n = out_len - 1
    return n * prompt_len + n * (n + 1) // 2


def decode_flops(w: dict, tokens: int, context_sum: int) -> float:
    """FLOPs of `tokens` decoded tokens that attend `context_sum` keys in
    all: 2 per weight per token, and 4 * q_dim per key per layer (scores
    and values)."""
    mp = matmul_params(w)
    q_dim = w["H"] * w["dh"]
    return (2.0 * (mp["layers"] + mp["head"]) * tokens
            + 4.0 * q_dim * w["L"] * context_sum)


def decode_bytes(w: dict, steps: int, context_sum: int,
                 kv_row_bytes: int) -> float:
    """Least bytes `steps` decode steps move: the weights once a step,
    and each attended cache row."""
    return float(weight_bytes(w)) * steps + float(kv_row_bytes) * context_sum
