"""Jaxpr-walking primitives for the static contract checker.

Everything here is pure introspection over `jax.make_jaxpr` output: no
compilation, no device execution. The central abstraction is a recursive
equation walk that descends into *every* sub-jaxpr an equation carries in
its params — `jit` bodies, `shard_map` bodies, `scan`/`while`/`cond`
branches, custom-vjp call jaxprs — yielding each equation together with
the *path* of enclosing higher-order primitives, so a pass can ask both
"does a psum appear anywhere?" and "is this all_gather inside a
shard_map?" without knowing the nesting rules of each primitive.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterator, Optional

import jax
import numpy as np
from jax.extend import core as jcore


def iter_subjaxprs(eqn) -> Iterator[tuple[str, "jcore.Jaxpr", tuple]]:
    """Yield (param_key, jaxpr, consts) for every sub-jaxpr in an
    equation's params — ClosedJaxpr values carry their consts, raw Jaxpr
    values (shard_map bodies, cond branches in some versions) carry none.
    Handles both bare values and tuples/lists of them."""
    for key, val in eqn.params.items():
        vals = list(val) if isinstance(val, (tuple, list)) else [val]
        for i, v in enumerate(vals):
            label = key if len(vals) == 1 else f"{key}[{i}]"
            if isinstance(v, jcore.ClosedJaxpr):
                yield label, v.jaxpr, tuple(v.consts)
            elif isinstance(v, jcore.Jaxpr):
                yield label, v, ()


def walk_eqns(jaxpr, path: tuple[str, ...] = ()
              ) -> Iterator[tuple[object, tuple[str, ...]]]:
    """DFS over every equation of `jaxpr` and all nested sub-jaxprs.

    Yields (eqn, path) where `path` is the tuple of enclosing primitive
    names ("jit", "shard_map", "scan", ...) from outermost to innermost.
    Accepts a Jaxpr or ClosedJaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, path
        for _, sub, _ in iter_subjaxprs(eqn):
            yield from walk_eqns(sub, path + (eqn.primitive.name,))


def prim_counts(jaxpr) -> Counter:
    """Histogram of primitive names over the full nested walk."""
    return Counter(eqn.primitive.name for eqn, _ in walk_eqns(jaxpr))


def find_prims(jaxpr, names) -> list[tuple[object, tuple[str, ...]]]:
    """All (eqn, path) whose primitive name is in `names`."""
    names = set(names)
    return [(eqn, path) for eqn, path in walk_eqns(jaxpr)
            if eqn.primitive.name in names]


def in_shard_map(path: tuple[str, ...]) -> bool:
    """True when the walk path passes through a shard_map body."""
    return "shard_map" in path


def collect_consts(closed, min_elems: int = 1
                   ) -> list[tuple[tuple[str, ...], object]]:
    """Every closure-captured constant in `closed` and all nested
    sub-jaxprs, as (path, const) — the HBM the trace pinned that is not
    an argument. `min_elems` filters scalars/small tables early."""
    out = []

    def visit(jaxpr, consts, path):
        for c in consts:
            if np.size(c) >= min_elems:
                out.append((path, c))
        for eqn in jaxpr.eqns:
            for label, sub, sub_consts in iter_subjaxprs(eqn):
                visit(sub, sub_consts,
                      path + (f"{eqn.primitive.name}:{label}",))

    visit(closed.jaxpr, tuple(closed.consts), ())
    return out


def outer_jit_eqn(closed) -> Optional[object]:
    """The single top-level jit equation of `jax.make_jaxpr(jitted_fn)`
    output — the equation whose params carry the jit's in/out shardings.
    None when the traced callable was not a jit wrapper."""
    eqns = closed.jaxpr.eqns if isinstance(closed, jcore.ClosedJaxpr) \
        else closed.eqns
    jits = [e for e in eqns if e.primitive.name == "jit"]
    return jits[0] if len(jits) == 1 else None


def is_unspecified(sharding) -> bool:
    """True for jit's UnspecifiedValue marker (no out_sharding pinned).
    Matched by type name — the class is private to `jax._src`."""
    return sharding is None or type(sharding).__name__ == "UnspecifiedValue"


def out_shardings_of(jit_eqn) -> tuple:
    """The flat out_shardings tuple a jit equation declares (one entry
    per flattened output leaf; UnspecifiedValue where unpinned)."""
    return tuple(jit_eqn.params.get("out_shardings", ()))


def spec_of(sharding):
    """The PartitionSpec of a NamedSharding-like object, else None."""
    return getattr(sharding, "spec", None)


def trace_jaxpr(fn, args, static_argnums=()):
    """`jax.make_jaxpr` with static argnums, returning the ClosedJaxpr.

    Trace only — nothing is lowered or compiled."""
    return jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(*args)
