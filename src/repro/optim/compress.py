"""Tensorized-RP gradient compression with error feedback.

The paper's map f_TT(R) / f_CP(R) gives an oblivious linear sketch whose
adjoint is an unbiased reconstruction (E[vec(S_i)vec(S_i)^T] = I). That makes
it a drop-in gradient compressor for the SLOW cross-pod axis:

  worker w:  p_w = g_w + e_w                   (error feedback)
             y_w = Sketch_t(p_w)               (k floats per 1M-float bucket)
             h_w = Unsketch_t(y_w)             (ONE adjoint pass per worker)
  network:   g_hat = mean_w h_w                (== Unsketch_t(mean_w y_w) by
                                                linearity of the adjoint)
  worker w:  e_w'  = p_w - h_w                 (local residual)

All workers regenerate the operator from fold_in(key, step) — the operator
itself (O(kNdR^2) floats) never crosses the network; the paper's memory bound
is exactly why the whole operator fits in VMEM/cache. NOTE the tradeoff in
the default mean_w h_w formulation (SketchCompressor(sync='local-mean')): it
halves per-worker adjoint compute (one unsketch instead of two), but the
sync point is a mean of DENSE reconstructions rather than of (buckets, k)
sketches. On a bandwidth-bound cross-pod link prefer sync='sketch-mean',
which restores the formulation that syncs y = mean_w y_w (~D/k times fewer
wire bytes) at the cost of every worker redundantly computing Unsketch_t(y);
`_metrics` reports `sketch_bytes` for THAT formulation's wire cost. Topology: params are
FSDP-sharded *within* a pod and replicated *across* pods (DiLoCo-style
DDP-of-FSDP), so the pod axis syncs via this compressed all-reduce.

Two formulations of the cross-pod sync coexist:

  * `compress_collective` — the REAL collective: a `shard_map` manual over
    the pod axis (auto over the rest) whose only cross-pod traffic is one
    `lax.pmean` (of the (buckets, k) sketches under sync='sketch-mean', of
    the dense reconstructions under 'local-mean'). This is what
    launch/steps.py wires into the train step on pod meshes.
  * `compress_per_pod` — the pure-pjit simulation of the same math via a
    leading npod dim (vmap(spmd_axis_name)); kept as the reference the
    collective is equivalence-tested against.

Fidelity/convergence are exercised in tests/benchmarks (CPU, small meshes);
the dry-run lowers the same code on the production mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.formats import BatchedCPTensor, BatchedTTTensor
from repro.core.sketch import PytreeSketcher, SketchConfig, _is_struct_leaf


def _balanced_pow2_dims(elems: int, order: int) -> tuple[int, ...]:
    """Tensorize a power-of-two bucket into `order` balanced pow2 modes.

    Spreads the exponent as evenly as possible, larger modes first —
    order=3 over the default 2^20 bucket reproduces the classic
    (128, 128, 64); order=4 gives (32, 32, 32, 32).
    """
    if order < 1:
        raise ValueError(f"order must be a positive integer, got {order}")
    e = elems.bit_length() - 1
    if elems <= 0 or (1 << e) != elems:
        raise ValueError(
            f"order= without dims= needs a power-of-two bucket, got {elems}")
    base, extra = divmod(e, order)
    if base == 0:
        raise ValueError(f"order={order} is too high for a {elems}-element "
                         "bucket (a mode would collapse to 1)")
    return tuple(1 << (base + (1 if i < extra else 0)) for i in range(order))


_FLAG_KEYS = ("dims", "k", "rank", "order")


def parse_compress_flag(flag: str) -> SketchConfig:
    """'<family>:k=4096,rank=2[,dims=128x128x64][,order=4]' -> SketchConfig.

    `family` is any registered repro.rp family ('tt', 'cp', 'gaussian',
    'sparse', ...); SketchConfig validates it against the registry.
    `order=N` without `dims=` tensorizes the default bucket into N balanced
    power-of-two modes (the order-N kernel path: same bucket/compression,
    smaller operator); with `dims=` it just cross-checks len(dims) == N.

    Unknown or malformed keys raise `ValueError` naming the bad key and the
    accepted set — a misspelled `rnak=4` must not silently ship the default
    rank to a production launch.
    """
    family, _, rest = flag.partition(":")
    kw: dict[str, Any] = {"family": family}
    order: int | None = None
    if rest:
        for part in rest.split(","):
            key, eq, val = part.partition("=")
            if not eq:
                raise ValueError(
                    f"malformed part {part!r} in compress flag {flag!r}: "
                    f"expected key=value with key in {_FLAG_KEYS}")
            if key not in _FLAG_KEYS:
                raise ValueError(
                    f"unknown key {key!r} in compress flag {flag!r}; "
                    f"accepted keys: {', '.join(_FLAG_KEYS)}")
            if key == "dims":
                dims = tuple(int(x) for x in val.split("x"))
                kw["dims"] = dims
                kw["bucket_elems"] = 1
                for d in dims:
                    kw["bucket_elems"] *= d
            elif key in ("k", "rank"):
                kw[key] = int(val)
            else:  # "order"
                order = int(val)
    if order is not None:
        if "dims" in kw:
            if len(kw["dims"]) != order:
                raise ValueError(
                    f"order={order} contradicts dims="
                    f"{'x'.join(map(str, kw['dims']))} (order "
                    f"{len(kw['dims'])})")
        else:
            elems = SketchConfig.__dataclass_fields__["bucket_elems"].default
            kw["dims"] = _balanced_pow2_dims(elems, order)
            kw["bucket_elems"] = elems
    return SketchConfig(**kw)


@dataclasses.dataclass
class SketchCompressor:
    cfg: SketchConfig
    pod_axis: str | None = None     # lax axis name inside shard_map
    base_key: int = 0x5EED
    # Cross-pod sync formulation for compress_per_pod (equal by linearity):
    #   'local-mean'  — ONE adjoint pass per pod; the sync point is the
    #                   pod-mean of the dense local reconstructions (cheapest
    #                   compute, dense bytes on the pod axis);
    #   'sketch-mean' — sync the (buckets, k) sketch-mean (k-sized bytes on
    #                   the wire), then every pod redundantly unsketches it
    #                   (second adjoint pass). Prefer when the pod link is
    #                   bandwidth-bound.
    sync: str = "local-mean"
    # Wire dtype of the cross-pod collective in `compress_collective`:
    #   'fp32' — the reference: pmean of float32 payloads;
    #   'int8' — scaled-int8 payloads + float32 scales on the wire
    #            (`rp.quantize_for_psum`): per-bucket-row absmax scales for
    #            'sketch-mean' (~4x fewer HLO-measured all-reduce bytes),
    #            per-leaf scalar scales for 'local-mean'. The quantization
    #            error lands in the synced estimate and is absorbed by the
    #            NEXT step's error feedback like any other sketch error; it
    #            is bounded by s/2 per element with s the shared scale.
    wire: str = "fp32"
    # Explicit bucket-axis layout for the sketcher (the sharded-engine path):
    # `mesh` + `bucket_spec` (a PartitionSpec whose first entry names the
    # mesh axes for the (n_buckets, ...) dim) replace the legacy global
    # `_constrain_buckets` guess. launch/steps.py fills these from
    # launch/sharding.py::bucket_specs; None keeps single-host behavior.
    mesh: Any = dataclasses.field(default=None, compare=False)
    bucket_spec: Any = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        if self.sync not in ("local-mean", "sketch-mean"):
            raise ValueError(f"unknown sync mode {self.sync!r}; expected "
                             "'local-mean' or 'sketch-mean'")
        if self.wire not in ("fp32", "int8"):
            raise ValueError(f"unknown wire dtype {self.wire!r}; expected "
                             "'fp32' or 'int8'")
    # (structure-key, sketcher) memo — the tree structure is fixed across
    # steps, so the flatten + family/registry validation in PytreeSketcher
    # runs once instead of on every compress/compress_per_pod trace.
    _sk_cache: tuple | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @staticmethod
    def _leaf_memo_key(leaf):
        if _is_struct_leaf(leaf):
            # structured leaves key on the CONTAINER contract the sketcher
            # validates (type, dims, bucket count, dtype) — not on the
            # flattened core/factor shapes, which vary with the input rank
            # even though the sketcher bookkeeping is rank-independent
            nb = leaf.batch if isinstance(
                leaf, (BatchedTTTensor, BatchedCPTensor)) else 1
            return (type(leaf).__name__, tuple(leaf.dims), nb,
                    jnp.dtype(leaf.dtype).name)
        return (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)

    def _sketcher(self, tree, *, plain: bool = False) -> PytreeSketcher:
        """Memoized PytreeSketcher for `tree`. `plain=True` disables ALL
        bucket-layout constraints (explicit mesh/spec AND the legacy global
        hint) — required inside shard_map bodies, where any sharding
        constraint on a partially-manual mesh hard-crashes XLA's SPMD
        partitioner (sharding.IsManualSubgroup check)."""
        mesh = None if plain else self.mesh
        spec = None if plain else self.bucket_spec
        # flatten with the sketcher's own leaf predicate so the memo key
        # matches what PytreeSketcher validates (TT/CP containers are leaves)
        leaves, treedef = jax.tree_util.tree_flatten(
            tree, is_leaf=_is_struct_leaf)
        key = (treedef, tuple(self._leaf_memo_key(l) for l in leaves),
               mesh, spec, plain)
        if self._sk_cache is not None and self._sk_cache[0] == key:
            return self._sk_cache[1]
        sk = PytreeSketcher(self.cfg, tree, mesh=mesh, bucket_spec=spec,
                            constrain=not plain)
        self._sk_cache = (key, sk)
        return sk

    def init_state(self, params) -> dict:
        return {"residual": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def _key(self, step):
        key = jax.random.PRNGKey(self.base_key)
        if self.cfg.fresh_per_step:
            key = jax.random.fold_in(key, step)
        return key

    def compress(self, grads, state, *, step) -> tuple[Any, dict, dict]:
        """Single-worker roundtrip estimator (no comm): sketch -> unsketch
        with error feedback. Used on meshes without a pod axis."""
        sk = self._sketcher(grads)
        key = self._key(step)
        p = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e,
                         grads, state["residual"])
        alpha = self.cfg.shrinkage()
        y = sk.sketch(p, key)                           # (buckets, k)
        g_hat = jax.tree.map(lambda x: alpha * x, sk.unsketch(y, key))
        new_residual = jax.tree.map(lambda pp, gh: pp - gh.astype(jnp.float32),
                                    p, g_hat)
        g_out = jax.tree.map(lambda gh, g: gh.astype(g.dtype), g_hat, grads)
        return g_out, {"residual": new_residual}, self._metrics(sk, new_residual)

    def compress_per_pod(self, grads_pp, state, *, step):
        """Cross-pod compressed all-reduce, pure-pjit SIMULATION.

        The vmap(spmd_axis_name) formulation `compress_collective` replaces
        on real pod meshes — kept as the reference implementation the
        shard_map collective is equivalence-tested against.

        grads_pp / state['residual']: every leaf has a leading npod dim
        (produced by jax.vmap(..., spmd_axis_name='pod') so the dim is
        sharded over the pod mesh axis). Each pod runs ONE adjoint pass (its
        local unsketch, needed for the error-feedback residual anyway); by
        linearity of the adjoint, unsketch(mean_w y_w) == mean_w
        unsketch(y_w), so with the default sync='local-mean' the synced
        estimate is the pod-mean of the local reconstructions and the
        redundant second reconstruction of the old unsketch(y_mean)
        formulation is gone; sync='sketch-mean' keeps that formulation for
        bandwidth-bound pod links (see the `sync` field / module docstring
        for the compute-vs-bandwidth tradeoff).
        Returns (synced grads WITHOUT pod dim, new_state, metrics).
        """
        if self.wire != "fp32":
            raise ValueError(
                f"compress_per_pod is the pure-pjit reference and has no "
                f"collective to quantize; wire={self.wire!r} is a "
                "compress_collective feature — use wire='fp32' here")
        example = jax.tree.map(lambda g: jax.ShapeDtypeStruct(g.shape[1:],
                                                              g.dtype),
                               grads_pp)
        sk = self._sketcher(example)
        key = self._key(step)
        p = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e,
                         grads_pp, state["residual"])
        alpha = self.cfg.shrinkage()
        y_pp = jax.vmap(lambda t: sk.sketch(t, key))(p)   # (npod, buckets, k)
        g_hat_local = jax.tree.map(
            lambda x: alpha * x,
            jax.vmap(lambda yy: sk.unsketch(yy, key))(y_pp))
        if self.sync == "local-mean":
            # == alpha * unsketch(mean(y_pp, 0)) by linearity, WITHOUT a
            # second adjoint pass; syncs dense bytes over the pod axis.
            g_hat = jax.tree.map(lambda gh: jnp.mean(gh, axis=0), g_hat_local)
        else:  # 'sketch-mean' (sync validated in __post_init__)
            y_mean = jnp.mean(y_pp, axis=0)       # k-sized wire bytes
            g_hat = jax.tree.map(lambda x: alpha * x,
                                 sk.unsketch(y_mean, key))
        new_residual = jax.tree.map(lambda pp, gh: pp - gh.astype(jnp.float32),
                                    p, g_hat_local)
        g_out = jax.tree.map(lambda gh, g: gh.astype(g.dtype),
                             g_hat, example)
        return g_out, {"residual": new_residual}, self._pod_metrics(
            sk, new_residual)

    def compress_collective(self, grads_pp, state, *, step, mesh=None):
        """Cross-pod compressed all-reduce as a REAL `shard_map` collective.

        The production formulation of `compress_per_pod` (which simulates
        the pod axis with `jax.vmap(..., spmd_axis_name)`): leaves of
        `grads_pp` / `state['residual']` carry a leading npod dim laid out
        over the mesh's pod axis; the shard_map is MANUAL over that axis
        (`auto` over every other mesh axis, so FSDP/TP layouts inside the
        body stay with the partitioner). Each pod sees only its local
        slice, regenerates the operator from `fold_in(key, step)` — the
        operator itself NEVER crosses the network — sketches its error-fed
        gradient, and the only cross-pod collective is one `lax.pmean`:

          sync='sketch-mean' — pmean of the (n_buckets, k) sketches:
              n_buckets * k floats on the wire, every pod redundantly
              unsketches the mean (second adjoint pass);
          sync='local-mean'  — pmean of the dense local reconstructions:
              dense bytes on the wire, ONE adjoint pass per pod.

        `wire='int8'` replaces the float pmean with a scaled-int8 `psum`
        plus a small float32 scale sync (`rp.quantize_for_psum`): the
        payload shrinks 4x on the wire, the shared pod-max scale keeps the
        integer sum overflow-proof and the dequantized mean bitwise
        identical on every pod, and the quantization error is absorbed by
        the next step's error feedback. Requires npod <= 127.

        Equal to `compress_per_pod` to fp32 tolerance by linearity of the
        adjoint (wire='fp32'; int8 adds the bounded quantization error).
        Returns (synced grads WITHOUT the pod dim — replicated across pods
        —, new_state, metrics); metrics are computed OUTSIDE the shard_map
        so no extra scalar collectives dilute the wire-bytes claim.
        """
        mesh = mesh if mesh is not None else self.mesh
        if mesh is None:
            raise ValueError("compress_collective needs a mesh (pass mesh= "
                             "or construct SketchCompressor(mesh=...))")
        axis = self.pod_axis or "pod"
        if axis not in mesh.axis_names:
            raise ValueError(f"pod axis {axis!r} not in mesh axes "
                             f"{mesh.axis_names}")
        npod = mesh.shape[axis]
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads_pp)[0]:
            # the body keeps local row 0 of each shard, so a leading dim
            # that is a LARGER multiple of npod would shard_map cleanly but
            # silently drop every other pod's gradient
            if leaf.shape[:1] != (npod,):
                raise ValueError(
                    f"grads_pp leaf {jax.tree_util.keystr(path)} has "
                    f"leading dim {leaf.shape[0] if leaf.ndim else None}, "
                    f"expected the pod-axis size {npod}; one row per pod")
        example = jax.tree.map(lambda g: jax.ShapeDtypeStruct(g.shape[1:],
                                                              g.dtype),
                               grads_pp)
        # plain sketcher: inside the (partially) manual shard_map body the
        # bucket layout over the auto axes belongs to the partitioner — an
        # explicit NamedSharding constraint there trips an XLA SPMD
        # partitioner CHECK (IsManualSubgroup) and aborts the process
        sk = self._sketcher(example, plain=True)
        key = self._key(step)
        alpha = self.cfg.shrinkage()
        if self.wire == "int8" and npod > 127:
            raise ValueError(
                f"wire='int8' supports at most 127 pods (the overflow-proof "
                f"clip qmax = 127 // npod would be 0), got npod={npod}")
        # runtime import: rp.shard imports nothing from optim, no cycle
        from repro.rp.shard import dequantize_psum, quantize_for_psum

        def _mean_over_pods(x, *, per_row):
            """pmean(x) over the pod axis in the configured wire dtype."""
            if self.wire == "fp32":
                return jax.lax.pmean(x, axis)
            q, s = quantize_for_psum(x, axis, npod, per_row=per_row)
            return dequantize_psum(jax.lax.psum(q, axis), s, npod)

        def body(g_pp, e_pp):
            g = jax.tree.map(lambda a: a[0], g_pp)    # local (1, ...) slice
            e = jax.tree.map(lambda a: a[0], e_pp)
            p = jax.tree.map(lambda gg, ee: gg.astype(jnp.float32) + ee,
                             g, e)
            y = sk.sketch(p, key)                     # (n_buckets, k) local
            # the local adjoint pass is needed for the EF residual anyway
            h_local = jax.tree.map(lambda x: alpha * x, sk.unsketch(y, key))
            if self.sync == "sketch-mean":
                # the ONLY wire bytes: one scale per bucket row under int8
                y_mean = _mean_over_pods(y, per_row=True)
                g_hat = jax.tree.map(lambda x: alpha * x,
                                     sk.unsketch(y_mean, key))
            else:  # 'local-mean' (sync validated in __post_init__)
                g_hat = jax.tree.map(
                    lambda h: _mean_over_pods(h, per_row=False), h_local)
            resid = jax.tree.map(
                lambda pp, h: (pp - h.astype(jnp.float32))[None], p, h_local)
            g_out = jax.tree.map(lambda gh, gref: gh.astype(gref.dtype),
                                 g_hat, g)
            return g_out, resid

        pod_specs = jax.tree.map(lambda _: P(axis), grads_pp)
        res_specs = jax.tree.map(lambda _: P(axis), state["residual"])
        out_specs = (jax.tree.map(lambda _: P(), example), res_specs)
        from repro.launch.mesh import auto_axes
        f = jax.shard_map(body, mesh=auto_axes(mesh),
                          in_specs=(pod_specs, res_specs),
                          out_specs=out_specs, axis_names=frozenset({axis}),
                          check_vma=False)
        g_out, new_residual = f(grads_pp, state["residual"])
        return g_out, {"residual": new_residual}, self._pod_metrics(
            sk, new_residual)

    def _pod_metrics(self, sk: PytreeSketcher, residual) -> dict:
        """Cross-pod metrics: the base set plus the per-step pod-link bytes
        of the ACTIVE (sync, wire) mode — sketch_bytes/dense_bytes alone
        describe the fp32 sketch-mean formulation and would misreport
        'local-mean' or int8 comm on dashboards."""
        metrics = self._metrics(sk, residual)
        metrics["wire_bytes"] = jnp.asarray(self.wire_bytes(sk), jnp.float32)
        return metrics

    def wire_bytes(self, sk: PytreeSketcher) -> int:
        """Analytic per-step pod-link payload of `compress_collective` for
        the active (sync, wire) mode — read from the plan layer's wire
        ledger (`rp.collective_wire_bytes`), the single accounting the
        `perf/wire` bench rows and HLO byte checks gate against."""
        from repro.rp.plan import collective_wire_bytes
        return collective_wire_bytes(
            sync=self.sync, wire=self.wire,
            sketch_bytes=sk.sketch_bytes(), dense_bytes=sk.dense_bytes(),
            n_buckets=sk.n_buckets, n_leaves=len(sk._shapes))

    def _metrics(self, sk: PytreeSketcher, residual) -> dict:
        return {
            "sketch_bytes": jnp.asarray(sk.sketch_bytes(), jnp.float32),
            "dense_bytes": jnp.asarray(sk.dense_bytes(), jnp.float32),
            "residual_norm": jnp.sqrt(sum(
                jnp.sum(jnp.square(r)) for r in jax.tree.leaves(residual))),
        }

    def compression_ratio(self, params) -> float:
        return self._sketcher(params).compression_ratio()
