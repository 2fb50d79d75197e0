"""Pytree sketching: tensorized RP over flat parameter/gradient buckets.

This is the systems integration of the paper: big flat vectors (gradients,
parameter deltas) are bucketed, each bucket is tensorized into an MXU-aligned
order-N tensor (`dims` may be any length — the mode-sweep kernels handle any
order >= 2, and higher order means smaller cores for the same bucket size:
TT/CP operator params scale with the SUM of the modes, not their product),
and projected with any registered `repro.rp` family —
f_TT(R) / f_CP(R) from the paper, or the gaussian/sparse baselines via
flat-vector dispatch. Because the operator is derived from a PRNG key,
distributed hosts regenerate it locally — the operator itself never crosses
the network (what else crosses depends on the consumer's sync formulation;
see optim/compress.py).

Used by:
  * optim/compress.py — error-feedback compressed cross-pod all-reduce,
  * SketchMonitor      — O(k) per-step parameter-drift telemetry.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from .formats import STRUCT_TYPES, BatchedCPTensor, BatchedTTTensor, _prod


def _is_struct_leaf(x) -> bool:
    """Pytree leaves the sketcher treats as already-compressed inputs: they
    are projected in the compressed domain (rp.project's carry-sweep route)
    rather than bucketized — their dims must equal SketchConfig.dims."""
    return isinstance(x, STRUCT_TYPES)


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    family: str = "tt"         # any registered repro.rp family
    k: int = 1024              # sketch size per bucket
    rank: int = 2              # R of the tensorized map
    bucket_elems: int = 128 * 128 * 64  # elements per bucket (1,048,576)
    # MXU-aligned tensorization; ANY length >= 1 (order-N buckets route
    # through the mode-sweep kernels; e.g. (32, 32, 32, 32) halves TT
    # operator memory vs (128, 128, 64) at the same bucket size)
    dims: tuple[int, ...] = (128, 128, 64)
    fresh_per_step: bool = True  # re-draw operator each step (EF-friendly)
    backend: str = "auto"      # repro.rp backend policy for projections
    fmt: dataclasses.InitVar[str | None] = None  # deprecated alias of family

    def __post_init__(self, fmt):
        if fmt is not None:
            warnings.warn("SketchConfig(fmt=...) is deprecated; use "
                          "family=...", DeprecationWarning, stacklevel=2)
            object.__setattr__(self, "family", fmt)
        if _prod(self.dims) != self.bucket_elems:
            # a typed error (not an assert): survives `python -O` and tells
            # the caller which knob to fix
            raise ValueError(
                f"prod(dims) = {_prod(self.dims)} for dims={self.dims} does "
                f"not equal bucket_elems={self.bucket_elems}; pass "
                f"bucket_elems={_prod(self.dims)} or retensorize dims to "
                "cover the bucket")
        from repro import rp  # function-level: core <-> rp import cycle
        rp.get_family(self.family)  # fail fast on unknown families

    # (fmt read-access is restored as a property after the class definition;
    # the dataclass captured the InitVar default before the override.)

    def spec(self):
        from repro import rp
        return rp.ProjectorSpec(family=self.family, k=self.k, dims=self.dims,
                                rank=self.rank, backend=self.backend)

    def shrinkage(self) -> float:
        """MMSE damping for the adjoint roundtrip x_hat = alpha * A^T A x.

        E||A^T A x||^2 ~= ||x||^2 (1 + c*D/k) with c the paper's Thm-1
        variance factor, so alpha* = 1/(1 + c*D/k). Without it the roundtrip
        is an EXPANSION for D > k/c and error feedback diverges; with it the
        compressor is (1-delta)-contractive, delta = alpha*.
        """
        from . import theory
        c = theory.variance_factor(self.family, N=len(self.dims),
                                   R=self.rank, D=self.bucket_elems)
        return 1.0 / (1.0 + c * self.bucket_elems / self.k)

    def operator(self, key):
        from repro import rp
        return rp.make_projector(self.spec(), key)

    def operator_params(self) -> int:
        from . import theory
        try:
            return theory.params_rp(self.family, self.k, self.dims, self.rank)
        except KeyError:
            # externally registered family: count a sampled instance
            return self.operator(jax.random.PRNGKey(0)).num_params()


# Deprecated read alias: cfg.fmt -> cfg.family.
SketchConfig.fmt = property(lambda self: self.family)


def _constrain_buckets(x):
    """LEGACY best-effort hint: shard the bucket dim over every available
    (non-manual) mesh axis from the global model-settings context — without
    this the ravel/concat path replicates the full flat gradient on every
    device at production scale. Sketchers constructed with an explicit
    `mesh`/`bucket_spec` (the sharded-engine path) never consult this."""
    from repro.models import settings as msettings  # runtime import: no cycle
    mesh = msettings.get().mesh
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    manual = msettings.get().manual_axes
    axes = tuple(a for a in mesh.axis_names if a not in manual)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or x.shape[0] % size != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(axes, *([None] * (x.ndim - 1)))))


class PytreeSketcher:
    """Sketches a fixed-structure pytree bucket-wise, PER LEAF.

    Leaves may be dense arrays (bucketized and tensorized to `cfg.dims`) OR
    already-compressed `TTTensor` / `CPTensor` / `BatchedTTTensor` /
    `BatchedCPTensor` containers with dims == `cfg.dims`: structured leaves
    are sketched in the compressed domain (the carry-sweep kernel route —
    the paper's "project without densifying" claim as a sketcher feature)
    and reconstruct to dense unbiased estimates.

    Per-leaf (vs one global ravel/concat) matters at production scale: a
    concatenated 67B-param flat vector forces XLA to materialize a replicated
    copy per device; per-leaf buckets reshape each (already sharded) tensor
    locally. The same operator is shared across buckets and leaves (disjoint
    coordinates keep per-bucket estimates unbiased; sharing keeps operator
    memory O(kNdR^2) regardless of model size).

    Fidelity/compute scaling (why bucket_elems is a knob): at fixed
    compression ratio r = D/(nb*k), the per-bucket error c*Db/k = c*r is
    independent of bucket size, while sketch FLOPs = R*D*Db/r shrink linearly
    with smaller buckets — prefer the smallest MXU-aligned bucket that keeps
    k reasonable.

    Sharding: pass `mesh` (and optionally `bucket_spec`, a PartitionSpec
    whose first entry names the mesh axes for the bucket dim) to pin the
    `(n_buckets, ...)` bucket arrays to an explicit layout — the
    sharded-engine contract used by `rp.sketch_tree_sharded` and
    `SketchCompressor.compress_collective`. Without a mesh the sketcher
    falls back to the legacy `_constrain_buckets` global-settings hint.
    Per-leaf divisibility is checked at constrain time: a leaf whose bucket
    count the spec's axes do not divide stays unconstrained rather than
    erroring.
    """

    def __init__(self, cfg: SketchConfig, example_tree: Any, *,
                 mesh=None, bucket_spec=None, constrain: bool = True):
        self.cfg = cfg
        # runtime import: launch/mesh imports only jax
        from repro.launch.mesh import auto_axes
        self.mesh = auto_axes(mesh)
        self.bucket_spec = bucket_spec
        # constrain=False disables ALL bucket-layout constraints, including
        # the legacy global-settings hint — required inside shard_map bodies
        # (compress_collective), where a with_sharding_constraint in a
        # partially-manual region aborts XLA even when it comes from the
        # ambient model-settings mesh rather than an explicit mesh=
        self.constrain = constrain
        leaves, treedef = jax.tree_util.tree_flatten(
            example_tree, is_leaf=_is_struct_leaf)
        self._treedef = treedef
        self._struct = [_is_struct_leaf(l) for l in leaves]
        self._shapes, self._sizes, self._dtypes, self._nb = [], [], [], []
        for leaf, is_struct in zip(leaves, self._struct):
            if is_struct:
                if tuple(leaf.dims) != tuple(cfg.dims):
                    raise ValueError(
                        f"structured leaf dims {tuple(leaf.dims)} != "
                        f"SketchConfig.dims {tuple(cfg.dims)}; tensorize "
                        "structured leaves to the sketch dims up front")
                nb = leaf.batch if isinstance(
                    leaf, (BatchedTTTensor, BatchedCPTensor)) else 1
                # a structured leaf IS its own bucket(s): one per batch item;
                # its dense estimate comes back in the leaf's own dtype,
                # like dense leaves
                self._shapes.append(((nb,) if nb > 1 else ()) + tuple(cfg.dims))
                self._sizes.append(nb * cfg.bucket_elems)
                self._dtypes.append(leaf.dtype)
                self._nb.append(nb)
            else:
                self._shapes.append(tuple(leaf.shape))
                self._sizes.append(int(_prod(leaf.shape)))
                self._dtypes.append(leaf.dtype)
                self._nb.append(
                    max(1, -(-self._sizes[-1] // cfg.bucket_elems)))
        self.n = sum(self._sizes)
        self.n_buckets = sum(self._nb)
        self.padded = self.n_buckets * cfg.bucket_elems

    # -- bucket-axis sharding --------------------------------------------
    def _constrain(self, x):
        """Pin the bucket dim of `x` to the explicit mesh/spec when the
        sketcher was constructed with one; legacy global hint otherwise;
        nothing at all when constrain=False (shard_map bodies)."""
        if not self.constrain:
            return x
        if self.mesh is None:
            return _constrain_buckets(x)
        # runtime import: no cycle — and reuse the shard module's spec
        # normalization so the pjit layout and the shard_map entry points
        # can never disagree on what an entry/axes-size means
        from repro.rp.shard import bucket_pspec, shard_entry
        from jax.sharding import NamedSharding, PartitionSpec
        spec = self.bucket_spec
        if spec is None:
            spec = bucket_pspec(self.mesh, x.shape[0])
        entry, _, size = shard_entry(self.mesh, spec)
        if size <= 1 or x.shape[0] % size:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh,
                             PartitionSpec(entry, *([None] * (x.ndim - 1)))))

    # -- per-leaf shaping -------------------------------------------------
    def _leaf_to_buckets(self, leaf, nb: int) -> jnp.ndarray:
        flat = leaf.reshape(-1).astype(jnp.float32)
        pad = nb * self.cfg.bucket_elems - flat.size
        if pad:
            # concatenate, NOT jnp.pad: a pad op inside a partially-manual
            # shard_map body (the compress_collective path) trips an XLA
            # SPMD-partitioner CHECK (hlo_sharding_util IsManualSubgroup)
            # and aborts the process; concatenate partitions cleanly
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return self._constrain(flat.reshape((nb,) + self.cfg.dims))

    def _leaf_from_buckets(self, buckets, size: int, shape, dtype):
        return buckets.reshape(-1)[:size].reshape(shape).astype(dtype)

    # -- sketch / unsketch -----------------------------------------------
    def sketch(self, tree: Any, key, *, project_fn=None) -> jnp.ndarray:
        """tree -> (n_buckets, k) sketch (buckets concatenated over leaves).

        All buckets of a leaf go through ONE batched `rp.project` call — on
        the Pallas route that is a single kernel launch with a native batch
        grid axis (operator cores streamed once per k-tile, not once per
        bucket), instead of the old vmap of per-bucket launches.

        Structured (TT/CP-format) leaves never densify: each one is
        projected in the compressed domain by the carry-sweep route, a
        batched container counting one bucket per batch item — still ONE
        dispatch per leaf.

        `project_fn(op, buckets) -> (nb, k)` overrides the dense-bucket
        projection call (the sharded engine passes a shard_map-wrapping
        closure — `rp.sketch_tree_sharded`); structured leaves always take
        the plain single-dispatch route.
        """
        from repro import rp
        op = self.cfg.operator(key)
        if project_fn is None:
            def project_fn(o, buckets):
                return rp.project(o, buckets, backend=self.cfg.backend)
        flat_op = len(op.in_dims) == 1  # gaussian/sparse contract flat
        ys = []
        leaves = jax.tree_util.tree_leaves(tree, is_leaf=_is_struct_leaf)
        for leaf, nb, is_struct in zip(leaves, self._nb, self._struct):
            if is_struct:
                y = rp.project(op, leaf, backend=self.cfg.backend)
                ys.append(y.reshape(nb, self.cfg.k))
                continue
            buckets = self._leaf_to_buckets(leaf, nb)
            if flat_op:
                buckets = buckets.reshape(nb, -1)
            ys.append(project_fn(op, buckets))
        return jnp.concatenate(ys, axis=0)

    def unsketch(self, y: jnp.ndarray, key) -> Any:
        """(n_buckets, k) -> unbiased pytree estimate (same key as sketch).

        One batched `rp.reconstruct` per leaf — the Pallas adjoint kernels
        reconstruct every bucket of the leaf in a single launch. Structured
        leaves come back as DENSE unbiased estimates (`(*dims)` for a
        single tensor, `(B, *dims)` for a batched container): the adjoint
        of a sketch is a dense tensor, there is no exact TT/CP form to
        return to.
        """
        from repro import rp
        op = self.cfg.operator(key)
        out = []
        off = 0
        for nb, size, shape, dtype in zip(self._nb, self._sizes,
                                          self._shapes, self._dtypes):
            buckets = rp.reconstruct(op, self._constrain(y[off:off + nb]),
                                     backend=self.cfg.backend)
            out.append(self._leaf_from_buckets(buckets, size, shape, dtype))
            off += nb
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def roundtrip(self, tree: Any, key) -> tuple[Any, jnp.ndarray]:
        """Returns (reconstruction, sketch)."""
        y = self.sketch(tree, key)
        return self.unsketch(y, key), y

    # -- accounting -------------------------------------------------------
    def sketch_bytes(self) -> int:
        return self.n_buckets * self.cfg.k * 4

    def dense_bytes(self) -> int:
        return self.n * 4

    def compression_ratio(self) -> float:
        return self.dense_bytes() / max(1, self.sketch_bytes())


# ---------------------------------------------------------------------------
# Sketch-based telemetry: parameter drift / gradient norms at O(k) cost.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SketchMonitor:
    """Tracks ||theta_t - theta_{t-1}|| and ||theta_t|| through a fixed sketch.

    By the JL property the sketch-space norms are (1±eps)-faithful; the state
    is n_buckets*k floats regardless of model size (e.g. 64 KB for a 7B model
    with k=1024, 1 bucket stride sampling).
    """

    sketcher: PytreeSketcher
    key: jax.Array
    prev: jnp.ndarray | None = None

    def update(self, tree: Any) -> dict[str, jnp.ndarray]:
        y = self.sketcher.sketch(tree, self.key)
        norm = jnp.sqrt(jnp.sum(y * y))
        if self.prev is None:
            drift = jnp.zeros((), y.dtype)
        else:
            d = y - self.prev
            drift = jnp.sqrt(jnp.sum(d * d))
        self.prev = y
        return {"sketch_norm": norm, "sketch_drift": drift}
