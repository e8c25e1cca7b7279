"""Output tokens of every request of every round over all the window's
time (host clock)."""
def read(ctx):
    return sum(len(r["tokens"]) for r in ctx["requests"]) / ctx["window_s"]
