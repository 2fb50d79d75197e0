"""Logical-axis -> mesh-axis sharding rules.

Parameters carry logical axis names (models/<family>.param_axes); this module
maps them to PartitionSpecs for a concrete mesh, with per-dimension
divisibility fallbacks (e.g. whisper's vocab 51865 is not divisible by 16, so
its vocab dim falls back to replicated — recorded via `notes`).

Strategy (see DESIGN.md §6):
  embed        -> (pod, data)   FSDP: ZeRO-3-style weight sharding
  heads/mlp/vocab -> model      tensor parallel
  experts      -> model         expert parallel (if E divides |model|)
  expert_mlp   -> model         only when experts don't shard (TP fallback)
  layers       -> None          (scan axis)
Activations: batch -> (pod, data); residual stream sequence-sharded over
`model` between blocks (sequence parallelism) via shard_batch_seq.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig

from .mesh import auto_axes, data_axes, model_size


def axis_rules(cfg: ArchConfig, mesh, *, fsdp_axes=None) -> dict[str, Any]:
    """fsdp_axes: override the parameter-sharding data axes. The gradient
    compressor sets ('data',) so params replicate across pods (DDP-of-FSDP)
    and the pod axis syncs through the sketched all-reduce only."""
    dp = fsdp_axes if fsdp_axes is not None else data_axes(mesh)
    ms = model_size(mesh)
    experts_shardable = (cfg.moe is not None
                         and cfg.moe.num_experts % ms == 0)
    return {
        "embed": dp,
        # embedding table: vocab rows FSDP-sharded, d_model TP-sharded —
        # keeps the backward scatter-add fully partitioned (a dp-sharded
        # d_model would collide with the token batch axis and XLA falls back
        # to a replicated (V, D) f32 scatter).
        "vocab_fsdp": dp,
        "embed_tp": "model",
        "heads": "model",
        "mlp": "model",
        "mlp2": None,
        "vocab": "model",
        "experts": "model" if experts_shardable else None,
        "expert_mlp": None if experts_shardable else "model",
        "layers": None,
        None: None,
    }


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def spec_for(shape: tuple[int, ...], axes: tuple, rules: dict, mesh,
             notes: list | None = None) -> P:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    entries = []
    for dim, logical in zip(shape, axes):
        mesh_axis = rules.get(logical, None)
        if mesh_axis is None:
            entries.append(None)
            continue
        if dim % _axis_size(mesh, mesh_axis) != 0:
            # try a prefix for tuple axes (e.g. ('pod','data') -> ('pod',))
            chosen = None
            if isinstance(mesh_axis, (tuple, list)):
                for cut in range(len(mesh_axis) - 1, 0, -1):
                    sub = tuple(mesh_axis[:cut])
                    if dim % _axis_size(mesh, sub) == 0:
                        chosen = sub
                        break
            if chosen is None and notes is not None:
                notes.append(f"dim {dim} !% {mesh_axis} -> replicated")
            entries.append(chosen)
        else:
            entries.append(tuple(mesh_axis) if isinstance(mesh_axis, list)
                           else mesh_axis)
    return P(*entries)


def param_specs(cfg: ArchConfig, axes_tree, mesh, shapes_tree,
                notes: list | None = None, *, fsdp_axes=None):
    """Pytree of PartitionSpecs matching the params tree."""
    rules = axis_rules(cfg, mesh, fsdp_axes=fsdp_axes)
    return jax.tree.map(
        lambda sds, ax: spec_for(sds.shape, ax, rules, mesh, notes),
        shapes_tree, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def bucket_specs(mesh, *, exclude: tuple = ()) -> P:
    """PartitionSpec template for `(n_buckets, ...)` sketch-bucket arrays.

    Shards the bucket dim over the mesh's data axes (the same axes
    `axis_rules` uses for FSDP), minus any axes under shard_map manual
    control (`exclude`, e.g. the 'pod' axis inside `compress_collective`).
    The sketcher applies per-leaf divisibility fallbacks, so a template
    whose axes don't divide some leaf's bucket count is safe.
    """
    axes = tuple(a for a in data_axes(mesh) if a not in exclude)
    return P(axes) if axes else P(None)


def batch_spec(shape: tuple[int, ...], mesh) -> P:
    """Shard dim 0 (global batch) over as many data axes as divide it."""
    dp = data_axes(mesh)
    n = shape[0]
    for cut in range(len(dp), -1, -1):
        sub = dp[:cut]
        size = int(np.prod([mesh.shape[a] for a in sub])) if sub else 1
        if n % size == 0:
            return P(sub if sub else None, *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def input_batch_specs(batch_tree, mesh):
    """Specs for a batch dict of ShapeDtypeStructs (tokens/labels/frames...).

    positions3 has batch on dim 1; everything else on dim 0.
    """
    def leaf_spec(path, sds):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "positions3":
            inner = batch_spec(sds.shape[1:], mesh)
            return P(None, *inner)
        return batch_spec(sds.shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf_spec, batch_tree)


# ---------------------------------------------------------------------------
# Decode-cache specs (per family layouts; see models/*.init_cache)
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, cache_tree, mesh):
    dp = data_axes(mesh)
    ms = model_size(mesh)

    def spec(path, sds):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = sds.shape
        b = shape[1] if len(shape) > 1 else 1
        bax = batch_spec((b,), mesh)[0]
        if name in ("k", "v", "xk", "xv") and len(shape) == 5:
            L, B, H, S, hd = shape
            if H % ms == 0:
                return P(None, bax, "model", None, None)
            if S % ms == 0:
                return P(None, bax, None, "model", None)
            return P(None, bax, None, None, None)
        if name == "pos" and len(shape) == 3:
            L, B, S = shape
            if S % ms == 0 and cfg.family == "hybrid":
                return P(None, bax, "model")
            # transformer pos buffer follows the k/v seq sharding only if
            # heads don't shard
            if cfg.n_kv_heads % ms != 0 and S % ms == 0:
                return P(None, bax, "model")
            return P(None, bax, None)
        if name == "ssm":                     # (L, B, H, P, ds)
            return P(None, bax, "model" if shape[2] % ms == 0 else None,
                     None, None)
        if name == "conv":                    # (L, B, W-1, C)
            return P(None, bax, None, "model" if shape[3] % ms == 0 else None)
        if name == "h":                       # (G, B, dr)
            return P(None, bax, "model" if shape[2] % ms == 0 else None)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def shard_batch_seq(x, mesh, *, seq_axis: int = 1, exclude: tuple = ()):
    """Sequence-parallel constraint on the residual stream (B, S, D).
    `exclude` drops axes under shard_map manual control (e.g. 'pod')."""
    dp = tuple(a for a in data_axes(mesh) if a not in exclude)
    entries = [None] * x.ndim
    entries[0] = dp if x.shape[0] % _axis_size(mesh, dp) == 0 else None
    if x.shape[seq_axis] % model_size(mesh) == 0:
        entries[seq_axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(auto_axes(mesh), P(*entries)))
