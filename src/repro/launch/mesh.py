"""Production meshes (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; everything else
sees the real single CPU device).
"""
from __future__ import annotations

import os

import jax


def ensure_host_platform_devices(n: int) -> None:
    """Ask XLA for ``n`` virtual host (CPU) devices by appending
    ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS`` — unless
    some count is already forced, which is respected. The single definition
    for every caller that self-provisions a mesh (tests/conftest.py,
    benchmarks/run.py --devices, the dmf_train CLI --n-shards).

    Must run before the first jax *device query*: importing jax (as this
    module does) is safe — only backend init binds the flags."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def auto_mesh(mesh):
    """``mesh`` with every axis typed Auto. ``jax.make_mesh`` types axes
    Explicit by default, and under Explicit axes every gather or
    contraction over a sharded dim must state its output sharding; the LM
    stack leaves that to GSPMD propagation, so its step builders re-type
    the mesh they are given."""
    from jax.sharding import AxisType, Mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips single pod; (2,16,16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 4, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    if multi_pod:
        return jax.make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def n_batch_shards(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
