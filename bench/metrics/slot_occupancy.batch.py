"""Engine / scheduler: decode tokens over decode steps x slots, from the
engine's counters, in %."""
def read(ctx):
    st = ctx["stats"]
    if st["decode_steps"] <= 0:
        return None
    return 100.0 * st["decode_tokens"] / (st["decode_steps"] * ctx["slots"])
