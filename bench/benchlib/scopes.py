"""Device time of the decode window by part of the model, from a profiler
trace whose operations carry the program's name scopes, and the three
metrics read from it.

On a TPU the scope path of an op is its `tf_op` stat (the op's metadata
`op_name`, e.g. `jit(_decode_window)/while/body/closed_call/decode_step/
while/body/closed_call/proj/dot_general:`). It is a stat of the op's event
*metadata*, which `jax.profiler.ProfileData` does not expose, so
`extract` reads it from the file's protobuf wire format itself.

Parts of the decode window's leaf ops (an op that holds other ops on the
line, such as a `while`, counts through them), clipped to the
`bench.window` span, by the innermost scope on the op's path:

- `proj`: the weight projections and the head (`layers.dense_proj`: XLA
  dots, or a `gemm_core` kernel and its epilogue);
- `flash_decode`: the flash-decode kernel (its `pallas_call` name);
- `attn_core`: the rest of `attn_core` (the kernel's glue, or the XLA
  fallback);
- `kv_write`: the cache row writes of `layers.attn_apply`;
- `pointwise`: the norms, rope, activation and residual adds;
- `decode_step`: the other ops inside `LM.decode_step`, above all the
  slices and updates of the stacked cache XLA adds around the layer
  loop (and its slices of the stacked weights, and the embedding
  lookup);
- `unscoped`: ops with no scope at all, which XLA inserts, and the
  copies outside the step and every part: above all XLA's copies of the
  loop-carried arena, which may carry no scope or borrow the loop's;
- `window`: the scoped ops of the fused window outside the step
  (sampling).

`extract` reads an `.xplane.pb` file into plain lists, as
`trace_reduce.extract` does; `parts` and the readers work on those lists
and on the run's counters alone, so a trimmed trace kept as JSON
exercises them in the tests.
"""
from __future__ import annotations

import bisect
import re

from benchlib import trace_reduce

KERNELS = ("flash_decode", "flash_decode_paged")
PARTS = ("proj", "kv_write", "attn_core", "pointwise")
STEP = "decode_step"
UNSCOPED = "unscoped"
OUTSIDE = "window"
WINDOW_PROGRAM = "_decode_window"
SCOPE_STAT = "tf_op"
MODULES_LINE = "XLA Modules"


def extract(path: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns, scope, program], ...]},
        "host": [[name, start_ns, dur_ns, meta], ...],
        "profile_start_ns": epoch ns of the trace's time 0}

    An op's program is the event of its plane's "XLA Modules" line that
    holds it (e.g. `jit__decode_window(<id>)`)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    op_scope = op_scopes(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    device, host, start = {}, [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get(MODULES_LINE, []))
            ops = lines.get(trace_reduce.OPS_LINE, [])
            named = op_scope.get(plane.name, [])
            if [n for n, _ in named] != [e.name for e in ops]:
                raise ValueError(f"{plane.name}: the ops read from the "
                                 "wire are not the trace's ops")
            device[plane.name] = [
                [trace_reduce._op_name(e.name), e.start_ns, e.duration_ns,
                 scope, _program(e, modules)]
                for e, (_, scope) in zip(ops, named)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace_reduce.HOST_PREFIXES):
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     dict(e.stats)])
    return {"device": device, "host": host, "profile_start_ns": start}


def _program(e, modules: list) -> str:
    i = bisect.bisect_right(modules, (e.start_ns, float("inf"), "")) - 1
    if i >= 0 and modules[i][1] >= e.start_ns + e.duration_ns:
        return modules[i][2]
    return ""


# ------------------------------------------------- protobuf wire format
def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i: int = 0, end: int | None = None):
    """(field number, value) of the message in b[i:end]: an int for a
    varint, (start, end) for a length-delimited field, None for a fixed
    one."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at {i}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(raw: bytes) -> dict:
    """{device plane: [(event metadata name, `tf_op` stat) of each event
    of its "XLA Ops" line, in order]} from a serialized XSpace.

    Each op is looked up by its event's metadata id, not by name: HLO op
    names are numbered per program, so one trace's programs can share a
    name under different scopes. Fields read: XSpace.planes 1; XPlane.name
    2, lines 3, event_metadata 4 and stat_metadata 5 (maps of key 1 to
    value 2); XLine.name 2, events 4; XEvent.metadata_id 1;
    XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7 (a string interned as a
    stat metadata's name)."""
    b = memoryview(raw)
    out = {}
    for f, plane in _fields(b):
        if f != 1:
            continue
        name, ops, metas, stat_names = "", [], {}, {}
        for g, v in _fields(b, *plane):
            if g == 2:
                name = _text(b, v)
            elif g == 3:
                line = dict(_fields(b, *v))
                if 2 in line and _text(b, line[2]) == trace_reduce.OPS_LINE:
                    ops = [dict(_fields(b, *w)).get(1, 0)
                           for h, w in _fields(b, *v) if h == 4]
            elif g in (4, 5):
                entry = dict(_fields(b, *v))
                if 2 not in entry:
                    continue
                if g == 4:
                    metas[entry.get(1, 0)] = entry[2]
                else:
                    meta = dict(_fields(b, *entry[2]))
                    if 1 in meta and 2 in meta:
                        stat_names[meta[1]] = _text(b, meta[2])
        if not name.startswith("/device:TPU:"):
            continue
        want = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        named = {}
        for key in set(ops):
            op, scope = "", ""
            if key in metas:
                for g, v in _fields(b, *metas[key]):
                    if g == 2:
                        op = _text(b, v)
                    elif g == 5:
                        st = dict(_fields(b, *v))
                        if st.get(1) in want:
                            scope = (_text(b, st[5]) if 5 in st
                                     else stat_names.get(st.get(7), ""))
            named[key] = (op, scope)
        out[name] = [named[key] for key in ops]
    return out


# ---------------------------------------------------------------- parts
_SPLIT = re.compile(r"[/;]")


_COPY = re.compile(r"%copy(-start|-done)?(\.\d+)?$")


def part_of(scope: str, op: str = "") -> str:
    """The part of an op (HLO name `op`) by the innermost part on its
    scope path. A copy outside the step and every part is XLA's copy of
    the window's loop-carried state, whatever scope it borrowed."""
    if not scope:
        return UNSCOPED
    comps = _SPLIT.split(scope)
    for c in reversed(comps):
        if c in KERNELS:
            return "flash_decode"
        if c in PARTS:
            return c
    if STEP in comps:
        return STEP
    return UNSCOPED if _COPY.match(op) else OUTSIDE


def leaves(ops: list) -> list:
    """The ops that hold no other op (ops on one line nest or are
    disjoint)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[1] + o[2]]


def parts(ev: dict, program: str = WINDOW_PROGRAM) -> dict:
    """Device seconds of the first chip's leaf ops in `program`, by part,
    inside the window."""
    t0, t1 = trace_reduce._window({"host": [h[:3] for h in ev["host"]]})
    planes = sorted(ev["device"])
    if not planes:
        raise ValueError("trace has no device operations")
    out = dict.fromkeys(("proj", "flash_decode", "attn_core", "kv_write",
                         "pointwise", STEP, UNSCOPED, OUTSIDE), 0.0)
    ops = [o for o in ev["device"][planes[0]] if program in o[4]]
    for op, s, d, scope, _ in leaves(ops):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out[part_of(scope, op)] += (b - a) / 1e9
    return out


# ---------------------------------------------------------------- readers
def _parts(ctx):
    p = ctx.get("parts")
    return p if p and sum(p.values()) > 0 else None


def kv_move_share(ctx) -> float | None:
    """Share of the decode window's device time that moves the cache
    rather than computing: the row writes, the ops of the step under no
    part's scope and the ops XLA adds with no scope, in %."""
    p = _parts(ctx)
    if p is None:
        return None
    return 100.0 * (p["kv_write"] + p[STEP] + p[UNSCOPED]) \
        / sum(p.values())


def _roofline(ctx, part: str, nbytes) -> float | None:
    p, st, peaks = _parts(ctx), ctx.get("trace_stats"), ctx.get("peaks")
    if p is None or not st or peaks is None or p[part] <= 0:
        return None
    n = nbytes(st)
    if not n:
        return None
    return 100.0 * n / (p[part] * peaks["hbm_bytes_per_s"])


def flash_decode_roofline(ctx) -> float | None:
    """Least bytes of the attended cache rows in the traced window
    (`kv_rows_attended` x the bytes a row holds, from the architecture
    module) over the flash-decode kernel's device time x HBM bandwidth,
    in %."""
    return _roofline(ctx, "flash_decode",
                     lambda st: ctx["arch"].kv_row_bytes(ctx["config"])
                     * st.get("kv_rows_attended", 0))


def proj_roofline(ctx) -> float | None:
    """Served weight bytes a decode step reads (from the architecture
    module) x the traced window's decode steps over `proj` device time x
    HBM bandwidth, in %."""
    return _roofline(ctx, "proj",
                     lambda st: ctx["arch"].weight_bytes(ctx)
                     * st.get("decode_steps", 0))
