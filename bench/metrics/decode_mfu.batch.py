"""Model step: model FLOPs of the window's decode tokens (2 per served
matmul weight, head included, embedding lookup excluded, and 4 x context
x q_dim per layer) over the engine's decode time x the chip's bf16 peak,
in %."""
from benchlib.readers import decode_mfu


def read(ctx):
    return decode_mfu(ctx)
