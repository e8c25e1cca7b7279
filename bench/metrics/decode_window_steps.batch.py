"""Engine / scheduler: decode steps per decode dispatch over the window
(the mean length of the engine's fused decode windows), from the
engine's counters; None where the engine counts no dispatches."""
def read(ctx):
    st = ctx["stats"]
    if not st.get("decode_dispatches"):
        return None
    return st["decode_steps"] / st["decode_dispatches"]
