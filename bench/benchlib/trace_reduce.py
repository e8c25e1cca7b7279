"""From a profiler trace to the device's busy time, the device operations
that took the most time, and the longest idle gaps with what the host
was doing in each.

`Tracer` records one window with JAX's profiler, marked on the host by a
`bench.window` span, and reads it back with the decode window's device
time by part of the model (`scopes.parts`). `extract` reads the
`.xplane.pb` file into plain event lists; `reduce` works on those lists
alone, so a trimmed trace kept as JSON exercises it in the tests.

Device operations are the events of each TPU plane's "XLA Ops" line.
Host spans are the benchmark's own annotations (`bench.*`, `engine.*`)
on the host plane. Busy time is the union of the operations' intervals
inside the window, averaged over the chips; an idle gap is a stretch of
the window in which no operation ran on the first chip, named by the
innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import shutil

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("bench.", "engine.")
OPS_LINE = "XLA Ops"
TOP = 10


class Tracer:
    """start/stop a profiler trace of one window, kept in
    `<root>/.bench_trace` until `reduce` has read it."""

    def __init__(self, root: str):
        self.dir = os.path.join(root, ".bench_trace")
        self.running = False
        self._span = None

    def start(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.running = True

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self) -> dict:
        """`reduce` of the trace, and its `parts`."""
        from benchlib import scopes
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no trace written under {self.dir}")
        try:
            return dict(reduce(extract(paths[0])),
                        parts=scopes.parts(scopes.extract(paths[0])))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def extract(path: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...]} from an xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [[_op_name(e.name), e.start_ns,
                                           e.duration_ns]
                                          for e in line.events]
            if plane.name not in device:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                                 f"{[ln.name for ln in plane.lines]}")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"device": device, "host": host}


def _op_name(name: str) -> str:
    """An HLO op's name without its instruction text ("%while.46 = (...)"
    -> "%while.46"), so that one op's events add up under one name."""
    return name.split(" = ", 1)[0]


def _window(ev: dict) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    return spans[0]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(ev: dict, top: int = TOP) -> dict:
    """busy_s, window_s, device_ops and idle_gaps of one traced window."""
    t0, t1 = _window(ev)
    planes = sorted(ev["device"])
    if not planes:
        raise ValueError("trace has no device operations")
    busy, per_op = [], {}
    merged0 = None
    for plane in planes:
        clipped = []
        for name, s, d in ev["device"][plane]:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                clipped.append((a, b))
                if plane == planes[0]:
                    per_op[name] = per_op.get(name, 0.0) + (b - a)
        merged = _merge(clipped)
        busy.append(sum(b - a for a, b in merged))
        if plane == planes[0]:
            merged0 = merged
    gaps, prev = [], t0
    for a, b in merged0 + [[t1, t1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    host = [(n, s, s + d) for n, s, d in ev["host"] if n != WINDOW_SPAN]

    def label(a, b):
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in host if s <= mid <= e]
        return min(cover)[1] if cover else "host:unannotated"

    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "device_ops": [[n, d / 1e9] for n, d in ops],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }
