"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS for 512 host devices
BEFORE calling it, real launches get the actual TPU topology.

Axes:
  pod   — slow inter-pod (DCN / cross-ICI) data parallelism; the gradient
          sketch compressor targets this axis.
  data  — in-pod data parallel + FSDP parameter sharding.
  model — tensor/expert/sequence parallel.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis Auto: the code places arrays with
    `with_sharding_constraint` and partially-manual `shard_map`, which
    refer to Auto axes only (`jax.make_mesh` defaults to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh):
    """The same devices and axis names as `mesh`, every axis Auto — what
    every sharding entry point normalizes a caller's mesh to (a mesh built
    by `jax.make_mesh` has Explicit axes). None passes through."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}; the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            "=512 before any jax import")
    return make_mesh(shape, axes, devices=devices[:need])


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        # typed error (not an assert): survives `python -O` and names the fix
        raise ValueError(
            f"model={model} must be a positive divisor of the {n} available "
            f"device(s); pick a model-parallel size that divides {n} (or "
            "force more host devices via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return make_mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
