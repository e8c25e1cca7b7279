"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches JAX
device state, so tests/benches keep their 1-CPU view and only dryrun.py
(which sets XLA_FLAGS first) sees 512 host devices.
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist (CPU smoke/tests): a 1D data mesh."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_subset_mesh(n: int, axes=("data", "model")):
    """A (n, 1) mesh over the FIRST n local devices.

    `jax.make_mesh` insists the axis product covers every device; the
    sharded-parity tests and scaling benches need a 1-device reference mesh
    and an n-device mesh side by side in one multi-device process, so this
    builds the Mesh directly from a device subset."""
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"requested {n} devices, host has {len(devs)}")
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape((n, 1)), axes)


def make_tp_mesh(n: int, axes=("data", "model")):
    """A (1, n) mesh over the FIRST n local devices — `model` carries n.

    The serving-engine complement of `make_subset_mesh` (which is
    data-major for DP/FSDP training): the TP engine shards attention
    heads / MLP hidden / the KV arena over `model`, and decode batches are
    tiny, so the whole device budget goes to tensor parallelism. Built
    directly from a device subset for the same reason as above — parity
    tests hold a 1-device reference engine and an n-device engine in one
    process."""
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"requested {n} devices, host has {len(devs)}")
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape((1, n)), axes)


def force_host_devices(n: int) -> None:
    """Give XLA's CPU backend `n` devices, so the multi-device paths run
    on a CPU host. Only where the program is held to the CPU
    (``JAX_PLATFORMS=cpu``): an accelerator host builds its meshes from
    the real chips, and the flag is never set there. Must run before the
    first device query; an explicit device-count flag in ``XLA_FLAGS``
    wins."""
    import os
    if n <= 1 or os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
