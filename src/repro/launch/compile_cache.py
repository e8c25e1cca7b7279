"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (`chip_smoke.py`, `serve`, `train`,
`benchmarks.run`): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here sets another directory; otherwise the cache lives
at a fixed ``.jax_cache/`` in the repository root. The path is part of
the cache key, so it never depends on a temp dir, a pid or the time.
Call `enable()` before the first compile: JAX fixes the directory when it
first uses the cache.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_counts = {"requests": 0, "hits": 0}


def _count(event: str, **_):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1


def enable() -> str:
    """Place the persistent cache by the rule above; returns the directory
    in use. Also starts counting cache lookups and hits (`counts()`)."""
    if not _counts.get("listening"):
        jax.monitoring.register_event_listener(_count)
        _counts["listening"] = 1
    path = os.environ.get(ENV)
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def counts() -> dict[str, int]:
    """Persistent-cache lookups and hits since `enable()`."""
    return {"requests": _counts["requests"], "hits": _counts["hits"]}
