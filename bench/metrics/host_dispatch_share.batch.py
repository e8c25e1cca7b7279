"""Engine (host): time the engine spent inside its jitted calls until
they returned (dispatch, before any wait) over the time it spent in its
driven iterations (`engine_s`), in %, from the engine's counters. The
engine's own time leaves out what its caller does between iterations,
such as writing a profiler trace; None where the engine counts neither."""
def read(ctx):
    st = ctx["stats"]
    s, e = st.get("dispatch_s"), st.get("engine_s")
    if s is None or not e:
        return None
    return 100.0 * s / e
