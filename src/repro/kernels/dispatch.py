"""Kernel backend registry: one place that decides how kernels execute.

Covers every routed op in `kernels/ops.py` — the GEMM family and the
flash-decode attention op (`decode_attn_op`).

Backends:
  pallas-tpu        — compiled Pallas kernels (MXU path; requires a TPU).
  pallas-interpret  — the same kernels through the Pallas interpreter
                      (bit-faithful to the kernel logic on any platform;
                      used by the parity tests and for debugging).
  xla-ref           — the pure-jnp oracle composition (`kernels/ref.py`
                      semantics). Default off-TPU: XLA's native dot is the
                      fastest correct implementation on CPU/GPU hosts.

Selection order (first match wins):
  1. explicit per-call ``backend=`` argument,
  2. legacy ``interpret=`` boolean (True -> pallas-interpret,
     False -> pallas-tpu),
  3. process-wide override (`set_backend()` / `use_backend()` /
     ``REPRO_KERNEL_BACKEND`` env var),
  4. the mesh the traced program runs on (`on_mesh(mesh)`, see
     `backend_for_mesh`),
  5. platform default: pallas-tpu on TPU hosts, xla-ref elsewhere.

The choice depends on the program's mesh, never on how many devices the
host shows: a one-chip program on a four-chip host runs the kernels.
"""
from __future__ import annotations

import contextlib
import os

import jax

BACKENDS = ("pallas-tpu", "pallas-interpret", "xla-ref")

_state = {"override": None, "mesh_default": None}


def available_backends() -> tuple[str, ...]:
    return BACKENDS


def platform_default() -> str:
    """pallas-tpu on a TPU host, xla-ref everywhere else (XLA's native
    dot is the fastest correct implementation on CPU/GPU hosts)."""
    return "pallas-tpu" if jax.default_backend() == "tpu" else "xla-ref"


def backend_for_mesh(mesh) -> str:
    """Backend for a program jitted over `mesh` (None = no mesh).

    A mesh spanning more than one device routes to the partitionable XLA
    path: a pallas_call is an opaque custom call with no GSPMD
    partitioning rule, so inside a sharded program it would all-gather
    the full weight per call. No mesh or a one-device mesh keeps the
    platform default, so the MXU kernels stay on the hot path whatever
    else the host holds. Kernels *inside* `shard_map` (`tp_gemm`,
    `tp_decode_attn`) are already partitioned and take the platform
    default directly."""
    if mesh is None or mesh.size == 1:
        return platform_default()
    return "xla-ref"


@contextlib.contextmanager
def on_mesh(mesh):
    """Scope the default backend (rule 4) to `mesh` while a program is
    traced — the trainer's and the TP engine's jits trace under it. An
    explicit or process-wide choice still wins."""
    prev = _state["mesh_default"]
    _state["mesh_default"] = backend_for_mesh(mesh)
    try:
        yield
    finally:
        _state["mesh_default"] = prev


def set_backend(name: str | None) -> None:
    """Process-wide backend override (None restores platform selection)."""
    if name is not None and name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"expected one of {BACKENDS}")
    _state["override"] = name


def get_backend_override() -> str | None:
    return _state["override"]


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped backend override (tests / benchmarks)."""
    prev = _state["override"]
    set_backend(name)
    try:
        yield
    finally:
        _state["override"] = prev


def resolve(backend: str | None = None,
            interpret: bool | str | None = None) -> str:
    """Resolve the effective backend name for one kernel call."""
    if backend is None and isinstance(interpret, str):
        # legacy positional slot carrying a backend name
        backend = interpret
        interpret = None
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown kernel backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        return backend
    if interpret is not None:
        return "pallas-interpret" if interpret else "pallas-tpu"
    if _state["override"] is not None:
        return _state["override"]
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r} is not one of "
                             f"{BACKENDS}")
        return env
    return _state["mesh_default"] or platform_default()
