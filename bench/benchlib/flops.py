"""Counting helpers shared by the architecture modules
(`bench/arch/<model_type>.py`), which count the operations and bytes of
their own decode steps."""
from __future__ import annotations


def decode_context_sum(prompt_len: int, out_len: int, window: int = 0
                       ) -> int:
    """Keys attended over a request's decode steps: the first output
    token comes from the prefill, step i (1..out_len-1) attends the
    prompt and i tokens, or the `window` latest of them where a sliding
    window is set."""
    n = out_len - 1
    if window > 0:
        return sum(min(prompt_len + i, window) for i in range(1, n + 1))
    return n * prompt_len + n * (n + 1) // 2


def context_sum(requests: list, window: int = 0) -> int:
    """`decode_context_sum` over every request of a run (`ctx["requests"]`)
    that has tokens."""
    return sum(decode_context_sum(r["prompt"].size, len(r["tokens"]), window)
               for r in requests if len(r["tokens"]) > 0)
