"""Mesh-aware sharded sketching: shard_map entry points over the bucket axis.

The paper's systems claim — the TT/CP operator is O(kNdR^2) floats, so every
host regenerates it from a PRNG key and only sketches cross the network — is
what makes *distributed* sketching cheap. This module is where that claim
becomes explicit SPMD: `project_sharded` / `sketch_tree_sharded` take a
`jax.sharding.Mesh` plus a bucket `PartitionSpec` and lay the `(n_buckets,
...)` axis out over the mesh with `shard_map`, so every device runs ONE
kernel dispatch on its local bucket slice (the operator is an explicitly
replicated input — P() on every core — never an implicit broadcast the
partitioner might materialize differently per backend).

Layering: this module knows nothing about launch/ axis conventions. The
default `bucket_pspec` shards over every mesh axis that divides the bucket
count; `launch/sharding.py::bucket_specs` narrows that to the data axes of
the production mesh, and `optim/compress.py::compress_collective` builds the
cross-pod compressed all-reduce on top (manual over the pod axis, `auto`
over the rest).

All entry points degrade gracefully: a spec that shards over nothing (or a
bucket count the mesh axes do not divide) falls back to the plain
un-shard_map'd `rp.project` call, so single-device tests and CPU examples
run the same code path end to end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import auto_axes

from .dispatch import project, reconstruct


def _axes_tuple(entry) -> tuple[str, ...]:
    """Normalize a PartitionSpec entry to a tuple of axis names."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _axes_size(mesh, axes: tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def shard_entry(mesh, spec) -> tuple:
    """(dim-0 spec entry, axes tuple, total shard size) for a bucket spec.

    The one place the `(n_buckets, ...)` spec convention is decoded — the
    shard_map entry points and `PytreeSketcher._constrain` all call this, so
    the pjit layout and the shard_map layout can never disagree on what a
    spec entry means.
    """
    entry = spec[0] if len(spec) else None
    axes = _axes_tuple(entry)
    return entry, axes, _axes_size(mesh, axes)


def bucket_pspec(mesh, n_buckets: int, *, axes=None, exclude=()) -> P:
    """PartitionSpec for a `(n_buckets, ...)` bucket array on `mesh`.

    Picks the largest prefix of `axes` (default: every mesh axis not in
    `exclude`) whose total size divides `n_buckets` and shards dim 0 over
    it; `P(None)` when nothing divides. Trailing dims are left unsharded —
    each bucket is one kernel-sized tensorized block.
    """
    cand = tuple(a for a in (axes if axes is not None else mesh.axis_names)
                 if a not in exclude)
    for cut in range(len(cand), 0, -1):
        sub = cand[:cut]
        if n_buckets % _axes_size(mesh, sub) == 0:
            return P(sub)
    return P(None)


def _sharded_apply(fn, op, x, *, mesh, spec, axes):
    """shard_map `fn(op, x_local)` with dim 0 of `x` laid out per `spec`."""
    op_specs = jax.tree.map(lambda _: P(), op)
    f = jax.shard_map(fn, mesh=auto_axes(mesh), in_specs=(op_specs, P(spec[0])),
                      out_specs=P(spec[0]), axis_names=frozenset(axes),
                      check_vma=False)
    return f(op, x)


def project_sharded(op, x, *, mesh, spec: P | None = None,
                    backend: str = "auto") -> jnp.ndarray:
    """`rp.project` with the bucket axis sharded over the mesh.

    x: `(n_buckets, *op.in_dims)` (or `(n_buckets, D)` for flat-contracting
    families). Each shard of the bucket axis runs ONE `rp.project` dispatch
    on its local buckets — the kernel's native batch grid axis does the rest
    — and the operator is an explicitly replicated shard_map input, so
    nothing but `x` is ever laid out over the wire. Returns the
    `(n_buckets, k)` sketch sharded the same way.

    `spec` defaults to `bucket_pspec(mesh, n_buckets)`; a spec (or bucket
    count) that shards over nothing falls back to the plain dispatch.
    """
    x = jnp.asarray(x)
    if spec is None:
        spec = bucket_pspec(mesh, x.shape[0])
    _, axes, size = shard_entry(mesh, spec)
    if size <= 1:
        return project(op, x, backend=backend)
    if x.shape[0] % size:
        raise ValueError(
            f"bucket count {x.shape[0]} is not divisible by mesh axes "
            f"{axes} (size {size}); pass a spec that divides it "
            "(bucket_pspec picks the largest valid prefix)")
    # per-shard plan reuse: every shard body dispatches the SAME local
    # shape, so resolving the plan for one shard here means every traced
    # body (and every re-trace at this shape) is a plan-cache hit
    from .plan import StructureSig, plan_execution
    plan_execution(op, StructureSig(structure="dense",
                                    batch=x.shape[0] // size),
                   backend=backend)

    def body(o, xl):
        return project(o, xl, backend=backend)

    return _sharded_apply(body, op, x, mesh=mesh, spec=spec, axes=axes)


def reconstruct_sharded(op, y, *, mesh, spec: P | None = None,
                        backend: str = "auto") -> jnp.ndarray:
    """Adjoint of `project_sharded`: `(n_buckets, k) -> (n_buckets, *dims)`.

    Same layout contract: one batched `rp.reconstruct` dispatch per shard of
    the bucket axis, operator replicated, output sharded like the input.
    """
    y = jnp.asarray(y)
    if spec is None:
        spec = bucket_pspec(mesh, y.shape[0])
    _, axes, size = shard_entry(mesh, spec)
    if size <= 1:
        return reconstruct(op, y, backend=backend)
    if y.shape[0] % size:
        raise ValueError(
            f"bucket count {y.shape[0]} is not divisible by mesh axes "
            f"{axes} (size {size}); pass a spec that divides it")
    # per-shard plan reuse (see project_sharded): one resolve, N shard hits
    from .plan import StructureSig, plan_execution
    plan_execution(op, StructureSig(structure="sketch",
                                    batch=y.shape[0] // size),
                   kind="reconstruct", backend=backend)

    def body(o, yl):
        return reconstruct(o, yl, backend=backend)

    return _sharded_apply(body, op, y, mesh=mesh, spec=spec, axes=axes)


# ---------------------------------------------------------------------------
# int8 wire quantization for collective sketch syncs
# ---------------------------------------------------------------------------

def quantize_for_psum(y: jnp.ndarray, axis_name: str, npod: int,
                      *, per_row: bool = True):
    """Scaled-int8 quantization safe to `lax.psum` over `axis_name`.

    Emits `(q, s)` with `q` int8 and `s` a float32 scale such that
    `q ~= round(y / s)` clipped to `[-qmax, qmax]` for
    `qmax = 127 // npod` — the clip makes the integer all-reduce
    OVERFLOW-PROOF: the sum of `npod` values each bounded by `qmax` is
    bounded by `npod * qmax <= 127`, so the s8 accumulator can never wrap
    regardless of reduction order. The scale is SHARED across the axis
    (a `lax.pmax` of the local absmax), so every pod quantizes onto the
    same grid and `dequantize_psum(psum(q), s, npod)` is exactly the mean
    of the quantized values — bitwise identical on every pod.

    `per_row=True` scales each leading-axis row by its own absmax (the
    (n_buckets, k) sketch layout: one scale per bucket row costs 4 bytes
    against the row's k payload bytes); `per_row=False` uses one scalar
    scale for the whole array (dense local-mean leaves).

    `jnp.round` (half-to-even) and the integer psum are both deterministic
    and order-independent, so the dequantized result is bitwise
    reproducible across runs and pod counts — the property the
    determinism test in tests/test_compress.py pins.
    """
    if npod > 127:
        raise ValueError(
            f"int8 wire quantization supports at most 127 pods (qmax = "
            f"127 // npod would be 0), got npod={npod}")
    qmax = 127 // npod
    if per_row:
        a = jnp.max(jnp.abs(y), axis=tuple(range(1, y.ndim)), keepdims=True)
    else:
        a = jnp.max(jnp.abs(y))
    a = jax.lax.pmax(a, axis_name)
    s = jnp.maximum(a, jnp.finfo(jnp.float32).tiny) / qmax
    q = jnp.clip(jnp.round(y / s), -qmax, qmax).astype(jnp.int8)
    return q, s


def dequantize_psum(q_sum: jnp.ndarray, s: jnp.ndarray,
                    npod: int) -> jnp.ndarray:
    """Mean-dequantize an int8 `lax.psum` result: q_sum * s / npod."""
    return q_sum.astype(jnp.float32) * s / npod


def sketch_tree_sharded(cfg, tree, key, *, mesh, spec: P | None = None,
                        sketcher=None) -> jnp.ndarray:
    """Whole-tree sketch with every leaf's bucket axis sharded over `mesh`.

    The sharded-engine formulation of `PytreeSketcher.sketch`: buckets are
    built per leaf exactly as the sketcher does (same padding, same
    tensorization), then projected through `project_sharded` — ONE kernel
    dispatch per leaf per shard, with a per-leaf divisibility fallback to
    the unsharded dispatch (ragged tail leaves still sketch correctly, they
    just run replicated). Structured (TT/CP-format) leaves keep their
    compressed-domain single-dispatch route.

    Returns the `(n_buckets, k)` sketch, buckets concatenated over leaves in
    the sketcher's canonical order — bit-compatible with
    `PytreeSketcher.sketch` under the same key (it IS the sketcher's loop,
    with the dense-bucket projection swapped for the shard_map one).
    """
    from repro.core.sketch import PytreeSketcher
    sk = sketcher if sketcher is not None else PytreeSketcher(
        cfg, tree, mesh=mesh, bucket_spec=spec)

    def project_fn(op, buckets):
        nb = buckets.shape[0]
        leaf_spec = spec if spec is not None else bucket_pspec(mesh, nb)
        _, _, size = shard_entry(mesh, leaf_spec)
        if size > 1 and nb % size == 0:
            return project_sharded(op, buckets, mesh=mesh, spec=leaf_spec,
                                   backend=sk.cfg.backend)
        return project(op, buckets, backend=sk.cfg.backend)

    return sk.sketch(tree, key, project_fn=project_fn)
