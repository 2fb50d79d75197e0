"""ExecutionPlan: the one plan/compile layer under every projection path.

Every `rp.project` / `rp.reconstruct` / `rp.project_many` / serve-tick
execution resolves through a frozen, hashable `ExecutionPlan` produced by
`plan_execution(op_spec, structure_sig, *, backend, pipeline)` and held in
an LRU plan cache keyed by the same jit-cache-stable signature `many.py`
buckets traffic on: (family, k, dims, rank) x (structure, batch, in_rank,
chunk) x (backend, pipeline) x routing environment. Dispatch is plan
lookup -> record stats -> execute; the policy that used to live in three
places (`dispatch._use_kernel`, the planners' inline checks, the
benchmarks' re-derived ledgers) lives HERE, once.

Dispatch matrix (input format x operator family -> route):

  dense/flat x tt/cp (2<=N<=MAX_ORDER)  mode-sweep kernel | einsum
  (*batch, k) sketch x tt/cp            mode-sweep adjoint kernel | einsum
  (Batched)TT/CP x tt/cp (2<=N)         carry-sweep kernel
                                        (`kernels.struct.struct_project`,
                                        all four pairings, ONE launch per
                                        batched call) | batched einsum refs
  (Batched)TT/CP x gaussian/sparse      densified (`x.full()`) flat einsum
  order outside [2, MAX_ORDER] x any    einsum, even under 'pallas'

Backend policy (`backend='auto' | 'pallas' | 'xla'`)
---------------------------------------------------
Dense-input projections of the TT/CP families at any kernel-supported
order (2 <= N <= `repro.kernels.MAX_ORDER`) have batched mode-sweep Pallas
kernels (`repro.kernels.tt_project` / `cp_project` — `(*batch, *dims)`
inputs run in ONE launch with a native batch grid axis, never vmap); the
adjoints route the same way through `tt_reconstruct` / `cp_reconstruct`
for `(*batch, k)` sketches; structured (TT/CP-format) inputs — single or
batched, any pairing with a TT/CP operator — route to the carry-sweep
kernels in `repro.kernels.struct` (compressed-domain projection,
O(k N d R R~ (R + R~)), never densifying). Routing:

* 'xla'    — always the einsum path.
* 'pallas' — always the kernel (operators outside the supported order
             range — order-1 classical Gaussian, order > MAX_ORDER — take
             the einsum path); interpret mode off-TPU. A kernel the planner
             cannot fit (`KernelPlanError`) raises.
* 'auto'   — the kernel iff the shapes are MXU-aligned (k a multiple of the
             128 lane width, every mode a multiple of the 8 sublanes, order
             >= 2) AND we are on real TPU hardware. Off-TPU the kernels
             only run in interpret mode — a validation device, not a fast
             path — so 'auto' stays on XLA there unless `force_pallas()` is
             active (which tests use to prove the routing). A kernel the
             planner cannot fit within the TPU's VMEM at the aligned tile
             floor takes the einsum route, with the planner's reason
             recorded in `rejected`.

`interpret` is decided here and nowhere else (`kernel_interpret`): the
kernels run compiled by Mosaic on a TPU backend and in interpret mode on
any other. Every kernel call — dispatch, the fused optimizer update, the
benchmarks — reads it from its plan.

`chunk` on reconstruct is part of the plan, not a warning: the kernel
route records `chunk_policy='folded'` (the planner's VMEM budget already
tiles k, so the requested bound is honored by the kernel's own k-tiling);
the einsum route records `'honored'` and threads `chunk` through to
`op.reconstruct`. Pass `backend='xla'` to make a specific chunk value
authoritative.

The plan carries a unified `CostLedger` — flops, analytic HBM bytes (the
SAME `sweep_hbm_bytes` / `struct_hbm_bytes` planner ledgers the kernels
are scheduled by), VMEM footprint, collective wire bytes, the operator
parameter count, and the paper's Thm-1 variance factor — so benchmarks,
rooflines, and the compressor read one ledger instead of re-deriving
three. `rp.explain(op, x)` returns the chosen plan with its rejected
alternatives and reasons: this docstring, executable.

Routing environment (`jax.default_backend()`, `force_pallas()` depth) is
part of the cache key, so a plan never outlives the conditions that chose
its route.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro.core.baselines import GaussianRP, VerySparseRP
from repro.core.cp_rp import CPRP
from repro.core.formats import (BatchedCPTensor, BatchedTTTensor, CPTensor,
                                TTTensor, _prod)
from repro.core.tt_rp import TTRP
from repro.core import theory

from .protocol import ProjectorSpec

# ---------------------------------------------------------------------------
# centralized backend / pipeline validation (the ONE typed check; dispatch,
# ProjectorSpec, ServeConfig and the planners all delegate here)
# ---------------------------------------------------------------------------

BACKENDS = ("auto", "pallas", "xla")
STRUCTURES = ("dense", "tt", "cp", "sketch")


def validate_backend(backend: str) -> str:
    """The single `backend=` check: returns it, or raises the one typed
    ValueError naming the accepted set. Survives `python -O`."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    return backend


def validate_pipeline(pipeline: str) -> str:
    """The single `pipeline=` check — delegates to the kernels layer, which
    owns the `PIPELINES` tuple the schedules implement."""
    # local import: repro.kernels is deliberately not a module-level dep
    from repro.kernels.ops import validate_pipeline as _vp
    return _vp(pipeline)


def pow2ceil(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) — the canonical shape-bucket
    rounding `project_many` pads batches/ranks with and the serve engine
    pre-plans against (same function => same plan-cache key)."""
    out = 1
    while out < max(int(n), floor):
        out *= 2
    return out


def structure_tag(payload) -> str:
    """'tt' | 'cp' | 'dense' — the canonical structure of ONE payload (the
    group key of `project_many` and the serve batcher's lane splitter)."""
    if isinstance(payload, (TTTensor, BatchedTTTensor)):
        return "tt"
    if isinstance(payload, (CPTensor, BatchedCPTensor)):
        return "cp"
    return "dense"


# ---------------------------------------------------------------------------
# the plan IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StructureSig:
    """Jit-cache-stable signature of WHAT is being executed.

    structure : 'dense' | 'tt' | 'cp' (structured input) | 'sketch'
                (reconstruct input).
    batch     : coalesced batch rows the dispatch will see (1 for a single
                payload; `project_many`/serve bucket to `pow2ceil(n, 8)`).
    in_rank   : structured-input rank as the carry-sweep planner sees it
                (TT: max bond rank incl. boundary 1s; CP: component rank);
                0 for dense/sketch.
    chunk     : reconstruct-only k-intermediate bound (None elsewhere).
    """

    structure: str = "dense"
    batch: int = 1
    in_rank: int = 0
    chunk: int | None = None

    def __post_init__(self):
        if self.structure not in STRUCTURES:
            raise ValueError(f"unknown structure {self.structure!r}; "
                             f"expected {STRUCTURES}")


@dataclasses.dataclass(frozen=True)
class CostLedger:
    """The unified analytic cost ledger of one planned execution.

    flops      : 2x multiply-add count for the WHOLE batch (per-item cost
                 times `plan.batch`), from `repro.core.theory`.
    hbm_bytes  : analytic HBM traffic — the kernel routes read the SAME
                 planner ledgers the schedules are budgeted by
                 (`sweep_hbm_bytes` / `struct_hbm_bytes` /
                 `fused_hbm_bytes`); einsum routes report the one-pass
                 lower bound (inputs + operator + outputs, streamed once).
    vmem_bytes : accounted per-kernel-instance VMEM footprint (0 on xla).
    wire_bytes : collective payload bytes (0 for local dispatch; the
                 compressed-all-reduce ledger via `collective_wire_bytes`).
    params     : operator parameter count (the paper's memory axis).
    var_factor : Thm-1 variance factor of the family at this order/rank.
    """

    flops: int
    hbm_bytes: int
    vmem_bytes: int
    wire_bytes: int
    params: int
    var_factor: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A fully-resolved, frozen, hashable execution decision.

    `route` is the RESOLVED backend ('pallas' | 'xla') under the requested
    `backend` policy and the routing environment; `rejected` names every
    alternative route with the reason it lost — `rp.explain` is just this
    field. `tiles`/`grid`/`vmem` come from the kernel planner actually
    used (`plan_contraction` / `plan_carry_sweep`); None/0 on the einsum
    route. `plan_id` is a short stable hash of the cache key, tagged onto
    the dispatch obs spans so traces join to exact routes.
    """

    plan_id: str
    family: str
    structure: str
    kind: str                      # 'project' | 'reconstruct' | 'update'
    order: int
    k: int
    batch: int
    dims: tuple
    rank: int
    in_rank: int
    backend: str                   # requested policy
    route: str                     # resolved 'pallas' | 'xla'
    kernel: str
    pipeline: str
    chunk: int | None
    chunk_policy: str              # 'n/a' | 'folded' | 'honored'
    tiles: tuple | None            # (tk, tb, ba) / (tk, tb)
    grid: tuple | None
    rejected: tuple                # ((route, reason), ...)
    cost: CostLedger
    interpret: bool                # kernel route: interpret mode (off-TPU)
    carry_bytes: int = 0           # structured routes: the (B, k, R·R~)
                                   # bond state replacing dense sweep temps

    def as_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["cost"] = self.cost.as_dict()
        return out

    def describe(self) -> str:
        """Markdown block for `rp.explain` / `obs_report --explain`."""
        c = self.cost
        lines = [
            f"### plan {self.plan_id}: {self.kind} "
            f"{self.family}/{self.structure} N={self.order}",
            "",
            f"* route: **{self.route}** (requested backend="
            f"'{self.backend}', pipeline='{self.pipeline}'"
            + (", interpret mode" if self.interpret and self.route == "pallas"
               else "") + ")",
            f"* kernel: {self.kernel}",
            f"* shape: k={self.k} dims={'x'.join(map(str, self.dims))} "
            f"rank={self.rank} batch={self.batch}"
            + (f" in_rank={self.in_rank}" if self.in_rank else ""),
        ]
        if self.tiles is not None:
            lines.append(f"* tiles: {self.tiles} grid={self.grid}")
        if self.carry_bytes:
            lines.append(f"* carry_bytes: {self.carry_bytes}")
        if self.kind == "reconstruct":
            lines.append(f"* chunk: {self.chunk} ({self.chunk_policy})")
        lines += [
            f"* cost: flops={c.flops} hbm_bytes={c.hbm_bytes} "
            f"vmem_bytes={c.vmem_bytes} wire_bytes={c.wire_bytes} "
            f"params={c.params} var_factor={c.var_factor:.2f}",
            "",
            "rejected alternatives:",
        ]
        for route, reason in self.rejected:
            lines.append(f"* {route}: {reason}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

_CACHE_CAP = 512


@dataclasses.dataclass
class PlanCacheStats:
    builds: int = 0
    hits: int = 0
    evictions: int = 0

    @property
    def lookups(self) -> int:
        return self.builds + self.hits

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def as_dict(self) -> dict:
        return {"builds": self.builds, "hits": self.hits,
                "evictions": self.evictions, "hit_rate": self.hit_rate}


_PLAN_CACHE: "OrderedDict[tuple, ExecutionPlan]" = OrderedDict()
_CACHE_STATS = PlanCacheStats()


def plan_cache_stats() -> PlanCacheStats:
    """The LIVE global plan-cache stats object (builds/hits/evictions)."""
    return _CACHE_STATS


def clear_plan_cache() -> None:
    """Drop every cached plan and reset the stats (tests/benchmarks)."""
    _PLAN_CACHE.clear()
    _CACHE_STATS.builds = 0
    _CACHE_STATS.hits = 0
    _CACHE_STATS.evictions = 0


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

# operator class -> family tag for plans/spans/breakdowns; third-party
# registered families fall back to their lowercased class name
_FAMILY_BY_TYPE = {TTRP: "tt", CPRP: "cp", GaussianRP: "gaussian",
                   VerySparseRP: "sparse"}
_TN_FAMILIES = ("tt", "cp")


def _family_tag(op) -> str:
    for cls, name in _FAMILY_BY_TYPE.items():
        if isinstance(op, cls):
            return name
    return type(op).__name__.lower()


def _order_tag(op) -> int:
    try:
        return int(op.order)
    except (AttributeError, TypeError):
        return len(tuple(op.in_dims))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_interpret() -> bool:
    """THE interpret decision: Pallas kernels compile for the chip on a TPU
    backend and run in interpret mode on any other. Plans carry it
    (`ExecutionPlan.interpret`); nothing else decides it."""
    return not _on_tpu()


def _aligned(k: int, dims: tuple) -> bool:
    """MXU alignment: k on the 128 lane width, >= 2 modes, every mode a
    multiple of the 8 sublanes — the 'auto' policy's hardware predicate."""
    return k % 128 == 0 and len(dims) >= 2 and all(d % 8 == 0 for d in dims)


@dataclasses.dataclass(frozen=True)
class _OpSig:
    """Jit-cache-stable signature of the OPERATOR side of a plan key."""

    family: str
    k: int
    dims: tuple
    rank: int
    order: int
    is_tn: bool


def _op_signature(op_spec) -> _OpSig:
    """Normalize an operator instance OR a `ProjectorSpec` to one key.

    Operator instances are authoritative (dispatch plans from them);
    spec-based plans (benchmarks, `obs_report --explain`) see the spec's
    dims, which for flat-vector families differ from the operator's
    single-mode `in_dims` — routing is identical either way (non-TN
    families have no kernel), only the cache keys differ.
    """
    if isinstance(op_spec, ProjectorSpec):
        family = op_spec.family
        is_tn = family in _TN_FAMILIES
        dims = tuple(op_spec.dims)
        return _OpSig(family=family, k=int(op_spec.k), dims=dims,
                      rank=int(op_spec.rank) if is_tn else 0,
                      order=len(dims), is_tn=is_tn)
    op = op_spec
    is_tn = isinstance(op, (TTRP, CPRP))
    return _OpSig(family=_family_tag(op), k=int(op.k),
                  dims=tuple(int(d) for d in op.in_dims),
                  rank=int(op.rank) if is_tn else 0,
                  order=_order_tag(op), is_tn=is_tn)


def struct_in_rank(x) -> int:
    """The structured-input rank exactly as the carry-sweep planner sees
    it: max TT bond rank (boundary 1s included) or the CP component rank."""
    if isinstance(x, (TTTensor, BatchedTTTensor)):
        return int(max(x.ranks))
    return int(x.rank)


def group_signature(op, payloads, *, bucket: bool = True) -> StructureSig:
    """The `StructureSig` a coalesced `project_many` group will dispatch.

    Computes — WITHOUT materializing the batch — the exact padded shape
    `many.py` produces for a homogeneous payload list: batch rows bucketed
    to `pow2ceil(n, 8)`, TT interior bond ranks / CP component ranks
    bucketed per-position to powers of two. The serve engine pre-plans
    with this signature, so its tick hits the SAME plan-cache entry the
    coalesced dispatch resolves — one plan build per lane shape, total.
    """
    payloads = list(payloads)
    if not payloads:
        raise ValueError("group_signature needs at least one payload")
    tags = {structure_tag(p) for p in payloads}
    if len(tags) > 1:
        raise ValueError(
            f"group_signature needs a structurally homogeneous group, got "
            f"{sorted(tags)}; split by structure_tag first")
    tag = tags.pop()
    b = pow2ceil(len(payloads), 8) if bucket else len(payloads)
    if tag == "dense":
        return StructureSig(structure="dense", batch=b)
    if tag == "tt":
        n_bonds = len(payloads[0].ranks)
        per_pos = [max(p.ranks[i] for p in payloads)
                   for i in range(n_bonds)]
        if bucket:
            per_pos = ([per_pos[0]]
                       + [pow2ceil(r) for r in per_pos[1:-1]]
                       + [per_pos[-1]])
        return StructureSig(structure="tt", batch=b,
                            in_rank=int(max(per_pos)))
    r = max(int(p.rank) for p in payloads)
    return StructureSig(structure="cp", batch=b,
                        in_rank=pow2ceil(r) if bucket else r)


# ---------------------------------------------------------------------------
# the resolver
# ---------------------------------------------------------------------------

def _force_pallas_active() -> bool:
    # local import: dispatch imports this module at module level
    from . import dispatch
    return dispatch.current_stats().force_pallas


def _resolve_route(backend: str, *, supported: bool, aligned: bool,
                   on_tpu: bool, force: bool) -> tuple[str, tuple]:
    """(route, rejected) under the backend policy — the old `_use_kernel`
    decision, with the losing route's reason made explicit."""
    if not supported:
        return "xla", (("pallas", "no mode-sweep kernel for this "
                        "(family, order): kernels cover tt/cp at "
                        "2 <= N <= MAX_ORDER"),)
    if backend == "pallas":
        return "pallas", (("xla", "backend='pallas' pins the kernel "
                           "route"),)
    if backend == "xla":
        return "xla", (("pallas", "backend='xla' pins the einsum route"),)
    if not aligned:
        return "xla", (("pallas", "'auto' needs MXU-aligned shapes "
                        "(k % 128 == 0, >= 2 modes, every mode % 8 == 0)"),)
    if on_tpu or force:
        return "pallas", (("xla", "'auto' on aligned shapes on TPU (or "
                           "under force_pallas()) selects the kernel"),)
    return "xla", (("pallas", "off-TPU the kernels only run in interpret "
                    "mode — a validation device, not a fast path; 'auto' "
                    "stays on XLA (force_pallas() overrides)"),)


def _xla_dense_hbm(sig_b: int, k: int, dims: tuple, params: int) -> int:
    """One-pass lower bound of the einsum route: x + operator + y."""
    return 4 * (sig_b * _prod(dims) + params + sig_b * k)


def _safe_params(family: str, k: int, dims: tuple, rank: int) -> int:
    try:
        return int(theory.params_rp(family, k, dims, max(1, rank)))
    except Exception:
        return int(k * _prod(dims))  # unknown registered family: dense-eq


def _safe_var_factor(family: str, order: int, rank: int, dims: tuple
                     ) -> float:
    try:
        return float(theory.variance_factor(family, N=order,
                                            R=max(1, rank), D=_prod(dims)))
    except Exception:
        return float(theory.variance_factor_gaussian())


def _kernel_name(op_sig: _OpSig, sig: StructureSig, kind: str, route: str,
                 pipeline: str) -> str:
    if route == "xla":
        return {"project": "einsum", "reconstruct": "einsum_adjoint"}[kind]
    if sig.structure in ("tt", "cp"):
        return ("carry_sweep_pipelined" if pipeline == "double"
                else "carry_sweep")
    if kind == "reconstruct":
        return f"{op_sig.family}_sweep_adjoint"
    return ("sweep_pipelined" if pipeline == "double"
            else f"{op_sig.family}_sweep")


def _build_plan(op_sig: _OpSig, sig: StructureSig, kind: str, backend: str,
                pipeline: str, on_tpu: bool, force: bool,
                key: tuple) -> ExecutionPlan:
    # local import: repro.kernels is deliberately not a module-level dep of
    # the rp layer's import graph (dispatch no longer imports it at all)
    from repro.kernels import ops as kops
    from repro.kernels.struct import plan as ksplan

    f, k, dims, rank = op_sig.family, op_sig.k, op_sig.dims, op_sig.rank
    order, b = op_sig.order, int(sig.batch)
    order_ok = kops.kernel_order_supported(order)
    supported = op_sig.is_tn and order_ok
    aligned = _aligned(k, dims)
    route, rejected = _resolve_route(backend, supported=supported,
                                    aligned=aligned, on_tpu=on_tpu,
                                    force=force)
    params = _safe_params(f, k, dims, rank)
    var = _safe_var_factor(f, order, rank, dims)
    tiles = grid = None
    vmem = 0
    carry = 0
    kplan = None
    if route == "pallas":
        try:
            if sig.structure in ("tt", "cp"):
                kplan = ksplan.plan_carry_sweep(f, sig.structure, k, b, dims,
                                                rank, sig.in_rank,
                                                pipeline=pipeline)
            else:
                kplan = kops.plan_contraction(f, kind, k, b, dims, rank,
                                              pipeline=pipeline)
        except kops.KernelPlanError as e:
            if backend == "pallas":
                raise
            route, rejected = "xla", (("pallas", f"kernel does not fit: "
                                       f"{e}"),)
    if sig.structure in ("tt", "cp"):
        # structured input x TT/CP operator: the carry sweep
        per_item = theory.flops_project_struct(f, sig.structure, k, dims,
                                               max(1, rank),
                                               max(1, sig.in_rank))
        flops = b * per_item
        carry = theory.mem_carry_struct(k, max(1, rank),
                                        max(1, sig.in_rank), batch=b)
        if route == "pallas":
            tiles, grid = (kplan.tk, kplan.tb), kplan.grid
            vmem = kplan.vmem_bytes
            hbm = ksplan.struct_hbm_bytes(kplan)
        else:
            in_elems = ksplan._core_elems(sig.structure, dims,
                                          max(1, sig.in_rank))
            hbm = 4 * (k * ksplan._core_elems(f, dims, max(1, rank))
                       + b * in_elems + b * k)
    else:
        if op_sig.is_tn:
            per_item = (theory.flops_project_dense_tt(k, dims, max(1, rank))
                        if f == "tt"
                        else theory.flops_project_dense_cp(k, dims,
                                                           max(1, rank)))
        else:
            # flat-vector families: 2 flops per stored parameter per item
            per_item = 2 * params
        flops = b * per_item
        if route == "pallas":
            tiles, grid = (kplan.tk, kplan.tb, kplan.ba), kplan.grid
            vmem = kplan.vmem_bytes
            hbm = kops.sweep_hbm_bytes(kplan)
        else:
            hbm = _xla_dense_hbm(b, k, dims, params)
    if kind == "reconstruct":
        chunk_policy = "folded" if route == "pallas" else "honored"
    else:
        chunk_policy = "n/a"
    plan_id = hashlib.blake2s(repr(key).encode(),
                              digest_size=6).hexdigest()
    return ExecutionPlan(
        plan_id=plan_id, family=f, structure=sig.structure, kind=kind,
        order=order, k=k, batch=b, dims=dims, rank=rank,
        in_rank=int(sig.in_rank), backend=backend, route=route,
        kernel=_kernel_name(op_sig, sig, kind, route, pipeline),
        pipeline=pipeline, chunk=sig.chunk, chunk_policy=chunk_policy,
        tiles=tiles, grid=grid, rejected=rejected,
        cost=CostLedger(flops=int(flops), hbm_bytes=int(hbm),
                        vmem_bytes=int(vmem), wire_bytes=0, params=params,
                        var_factor=var),
        carry_bytes=int(carry), interpret=kernel_interpret())


def plan_execution(op_spec, structure_sig: StructureSig | None = None, *,
                   kind: str = "project", backend: str = "auto",
                   pipeline: str = "serial",
                   force_pallas: bool | None = None) -> ExecutionPlan:
    """Resolve (or fetch from the LRU cache) the `ExecutionPlan` for one
    execution of `op_spec` (an operator instance or a `ProjectorSpec`)
    against `structure_sig` (defaults to a single dense payload).

    This is THE resolver: backend/pipeline validation happens here once,
    the route decision replicates the dispatch policy bitwise (see the
    module docstring), and the returned plan carries the unified cost
    ledger. The cache key includes the routing environment
    (`jax.default_backend()`, `force_pallas()` — pass `force_pallas=` to
    pin it explicitly), so cached plans cannot outlive the conditions
    that chose their route.
    """
    validate_backend(backend)
    validate_pipeline(pipeline)
    if kind not in ("project", "reconstruct"):
        raise ValueError(f"unknown kind {kind!r}; expected "
                         "('project', 'reconstruct')")
    sig = structure_sig if structure_sig is not None else StructureSig()
    if kind == "reconstruct" and sig.structure != "sketch":
        raise ValueError(
            f"kind='reconstruct' plans take structure='sketch' signatures, "
            f"got {sig.structure!r}")
    op_sig = _op_signature(op_spec)
    if sig.structure in ("tt", "cp") and not op_sig.is_tn:
        raise ValueError(
            f"structured ({sig.structure!r}) execution plans exist for "
            f"tt/cp operators only; {op_sig.family!r} operators densify "
            "first (plan the resulting dense signature instead)")
    force = _force_pallas_active() if force_pallas is None else force_pallas
    key = (op_sig, sig, kind, backend, pipeline, _on_tpu(), bool(force))
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _PLAN_CACHE.move_to_end(key)
        _CACHE_STATS.hits += 1
        return cached
    plan = _build_plan(op_sig, sig, kind, backend, pipeline, _on_tpu(),
                       bool(force), key)
    _CACHE_STATS.builds += 1
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _CACHE_CAP:
        _PLAN_CACHE.popitem(last=False)
        _CACHE_STATS.evictions += 1
    return plan


# ---------------------------------------------------------------------------
# signature builders used by dispatch (operator + concrete input -> sig)
# ---------------------------------------------------------------------------

def dense_signature(op, xt) -> StructureSig:
    """Signature of a COERCED dense input `(*batch, *op.in_dims)`."""
    n = len(tuple(op.in_dims))
    return StructureSig(structure="dense",
                        batch=int(_prod(xt.shape[:-n])) if xt.ndim > n
                        else 1)


def struct_signature(op, x) -> StructureSig:
    """Signature of a structured (TT/CP-format) input, single or batched."""
    del op
    batch = int(x.batch) if isinstance(
        x, (BatchedTTTensor, BatchedCPTensor)) else 1
    return StructureSig(structure=structure_tag(x), batch=batch,
                        in_rank=struct_in_rank(x))


def sketch_signature(op, y, chunk: int | None = None) -> StructureSig:
    """Signature of a reconstruct input `(*batch, k)`."""
    del op
    return StructureSig(structure="sketch",
                        batch=int(_prod(y.shape[:-1])) if y.ndim > 1 else 1,
                        chunk=chunk)


# ---------------------------------------------------------------------------
# execution: the plan's route, run (owns every kernels import)
# ---------------------------------------------------------------------------

def execute_plan(plan: ExecutionPlan, op, x):
    """Run one planned execution. `x` is the dispatch-normalized input:
    a coerced dense array, a structured container, or a sketch array."""
    if plan.kind == "reconstruct":
        return _exec_reconstruct(plan, op, x)
    if plan.structure in ("tt", "cp"):
        return _exec_struct_project(plan, op, x)
    return _exec_dense_project(plan, op, x)


def _exec_dense_project(plan: ExecutionPlan, op, xt):
    if plan.route == "xla":
        return op.project(xt)
    from repro.kernels import ops as kops
    kern = kops.tt_project if plan.family == "tt" else kops.cp_project
    n = plan.order
    if xt.ndim <= n + 1:  # single input/1-D batch: native batch axis
        return kern(op, xt, interpret=plan.interpret, pipeline=plan.pipeline)
    batch = xt.shape[:-n]
    flat = xt.reshape((-1,) + xt.shape[-n:])
    return kern(op, flat, interpret=plan.interpret,
                pipeline=plan.pipeline).reshape(batch + (op.k,))


def _exec_struct_project(plan: ExecutionPlan, op, x):
    from repro.kernels import struct as kstruct
    if plan.route == "pallas":
        return kstruct.struct_project(op, x, interpret=plan.interpret,
                                      pipeline=plan.pipeline)
    return kstruct.struct_project(op, x, interpret=plan.interpret,
                                  use_kernel=False)


def _exec_reconstruct(plan: ExecutionPlan, op, y):
    chunk = plan.chunk
    if plan.route == "pallas":
        # chunk_policy='folded': the planner's VMEM budget already tiles k
        # (plan.tiles[0]), so the requested bound is honored by the
        # kernel's own k-tiling — no dense (D, k) intermediate exists
        from repro.kernels import ops as kops
        kern = (kops.tt_reconstruct if plan.family == "tt"
                else kops.cp_reconstruct)
        if y.ndim <= 2:
            return kern(op, y, interpret=plan.interpret)
        batch = y.shape[:-1]
        out = kern(op, y.reshape(-1, op.k), interpret=plan.interpret)
        return out.reshape(batch + tuple(op.in_dims))
    if y.ndim == 1:
        return op.reconstruct(y, chunk=chunk)
    batch = y.shape[:-1]
    out = jax.vmap(lambda yy: op.reconstruct(yy, chunk=chunk))(
        y.reshape(-1, op.k))
    return out.reshape(batch + tuple(op.in_dims))


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------

def explain(op, x, *, kind: str = "project", backend: str = "auto",
            pipeline: str = "serial",
            chunk: int | None = None) -> ExecutionPlan:
    """The `ExecutionPlan` that `rp.project` / `rp.reconstruct` would
    resolve for `(op, x)` — route, kernel, tiles, the unified cost ledger,
    and the REJECTED alternatives with reasons (the dispatch matrix in
    this module's docstring, executable). Pure: nothing is executed, but
    the plan lands in the same cache the real dispatch reads, so asking
    is also prewarming.

    `x` may be anything `project` accepts (dense/flat array, (Batched)
    TT/CP container) or, for `kind='reconstruct'`, a `(*batch, k)` sketch.
    Mirrors dispatch exactly: a structured input under a flat-vector
    operator densifies, so it is explained as the dense plan it executes.
    """
    if kind == "reconstruct":
        y = jnp.asarray(x)
        return plan_execution(op, sketch_signature(op, y, chunk),
                              kind="reconstruct", backend=backend)
    if isinstance(x, (TTTensor, CPTensor, BatchedTTTensor, BatchedCPTensor)):
        op_sig = _op_signature(op)
        if op_sig.is_tn:
            return plan_execution(op, struct_signature(op, x),
                                  backend=backend, pipeline=pipeline)
        batch = (int(x.batch)
                 if isinstance(x, (BatchedTTTensor, BatchedCPTensor)) else 1)
        sig = StructureSig(structure="dense", batch=batch)
        return plan_execution(op, sig, backend=backend, pipeline=pipeline)
    from .dispatch import _coerce_dense
    xt = _coerce_dense(op, jnp.asarray(x))
    return plan_execution(op, dense_signature(op, xt), backend=backend,
                          pipeline=pipeline)


# ---------------------------------------------------------------------------
# update (fused unsketch+EF+AdamW) and collective wire ledgers
# ---------------------------------------------------------------------------

def plan_update(op_spec, batch: int, *, fused: bool = True) -> ExecutionPlan:
    """The `ExecutionPlan` of one fused unsketch+EF+AdamW launch over
    `batch` buckets (or of the UNFUSED reconstruct -> EF -> AdamW chain
    when `fused=False` — same reconstruct-sweep plan, nine extra dense
    passes in the ledger). `cost.hbm_bytes` is the analytic traffic the
    perf benches gate (`fused_hbm_bytes` / `unfused_hbm_bytes`)."""
    from repro.kernels import fused_update as kfused

    op_sig = _op_signature(op_spec)
    if not op_sig.is_tn:
        raise ValueError(
            f"plan_update needs a tt/cp operator (the fused kernel IS the "
            f"reconstruct sweep), got family {op_sig.family!r}")
    sig = StructureSig(structure="sketch", batch=int(batch))
    kind = "update" if fused else "update-unfused"
    key = (op_sig, sig, kind, "pallas", "serial", _on_tpu(), False)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _PLAN_CACHE.move_to_end(key)
        _CACHE_STATS.hits += 1
        return cached
    fplan = kfused.plan_fused_update(op_sig.family, op_sig.k, int(batch),
                                     op_sig.dims, op_sig.rank)
    hbm = (kfused.fused_hbm_bytes(fplan) if fused
           else kfused.unfused_hbm_bytes(fplan))
    plan = ExecutionPlan(
        plan_id=hashlib.blake2s(repr(key).encode(),
                                digest_size=6).hexdigest(),
        family=op_sig.family, structure="sketch", kind=kind,
        order=op_sig.order, k=op_sig.k, batch=int(batch), dims=op_sig.dims,
        rank=op_sig.rank, in_rank=0, backend="pallas",
        route="pallas" if fused else "xla",
        kernel="fused_update" if fused else "unfused_chain",
        pipeline="serial", chunk=None, chunk_policy="folded",
        tiles=(fplan.tk, fplan.tb, fplan.ba), grid=fplan.grid,
        rejected=((("xla", "fused path requested: the dense gradient "
                    "estimate never touches HBM"),) if fused
                  else (("pallas", "unfused chain requested for "
                         "comparison"),)),
        cost=CostLedger(
            flops=int(batch) * int(
                theory.flops_project_dense_tt(op_sig.k, op_sig.dims,
                                              max(1, op_sig.rank))
                if op_sig.family == "tt"
                else theory.flops_project_dense_cp(op_sig.k, op_sig.dims,
                                                   max(1, op_sig.rank))),
            hbm_bytes=int(hbm), vmem_bytes=int(fplan.vmem_bytes),
            wire_bytes=0,
            params=_safe_params(op_sig.family, op_sig.k, op_sig.dims,
                                op_sig.rank),
            var_factor=_safe_var_factor(op_sig.family, op_sig.order,
                                        op_sig.rank, op_sig.dims)),
        interpret=kernel_interpret())
    _CACHE_STATS.builds += 1
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _CACHE_CAP:
        _PLAN_CACHE.popitem(last=False)
        _CACHE_STATS.evictions += 1
    return plan


def collective_wire_bytes(*, sync: str, wire: str, sketch_bytes: int,
                          dense_bytes: int, n_buckets: int,
                          n_leaves: int) -> int:
    """Analytic per-step pod-link payload of the compressed all-reduce —
    the plan layer's wire ledger, which `SketchCompressor.wire_bytes`
    reads. 'sketch-mean' syncs the (nb, k) sketches, 'local-mean' the
    densified tree; int8 payloads carry their float32 scales (one per
    bucket row under 'sketch-mean', one per leaf under 'local-mean')."""
    payload = sketch_bytes if sync == "sketch-mean" else dense_bytes
    if wire == "fp32":
        return int(payload)
    scales = n_buckets if sync == "sketch-mean" else n_leaves
    return int(payload) // 4 + 4 * int(scales)


__all__ = [
    "BACKENDS", "CostLedger", "ExecutionPlan", "PlanCacheStats",
    "StructureSig", "clear_plan_cache", "collective_wire_bytes",
    "dense_signature", "execute_plan", "explain", "group_signature",
    "kernel_interpret",
    "plan_cache_stats", "plan_execution", "plan_update", "pow2ceil",
    "sketch_signature", "struct_in_rank", "struct_signature",
    "structure_tag", "validate_backend", "validate_pipeline",
]
