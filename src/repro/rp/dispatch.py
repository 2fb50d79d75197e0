"""Structure-dispatched projection: plan lookup -> record -> execute.

`project(op, x)` is the single entry point replacing the old
`project` / `project_tt` / `project_cp` method zoo: it inspects the input's
structure (dense tensor, flat vector, `TTTensor` / `CPTensor`, or the
batched `BatchedTTTensor` / `BatchedCPTensor` containers), raising a typed
`FormatMismatchError` on incompatible shapes — and then EVERY execution
resolves through a cached `repro.rp.plan.ExecutionPlan`: the dispatch
matrix, backend policy, kernel/tile/pipeline selection and the unified
cost ledger all live in `plan.py` (see its module docstring — or run
`rp.explain(op, x)`, which returns the resolved plan with its rejected
alternatives). This module keeps only input normalization and the
context-local instrumentation.

Instrumentation is CONTEXT-LOCAL: a `DispatchStats` object held in a
`contextvars.ContextVar` carries the kernel-dispatch counter, the
per-(family, structure, route, order) launch `breakdown`, and the
force-pallas depth. `kernel_call_count()` reads the current context's
counter (counted at trace time — cached jit executions don't re-dispatch);
`dispatch_stats()` installs a fresh, isolated object for a dynamic scope so
parallel tests and nested contexts can't corrupt each other's counts, and
`force_pallas()` is depth-counted so nesting composes.

Every dispatch additionally opens a `repro.obs` span (`rp.project` /
`rp.reconstruct`, tagged family/structure/order/backend/pipeline with the
RESOLVED route plus the `plan` id, so traces join to exact routes) — a
shared no-op when telemetry is disabled, so the hot path pays one
module-global read (gated by the obs/overhead bench row).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax.numpy as jnp

from repro import obs
from repro.core.cp_rp import CPRP
from repro.core.formats import (STRUCT_TYPES, BatchedCPTensor,
                                BatchedTTTensor, _prod)
from repro.core.tt_rp import TTRP

from . import plan as _plan
from .protocol import FormatMismatchError, RPOperator


@dataclasses.dataclass
class DispatchStats:
    """Context-local dispatch instrumentation.

    kernel_calls : number of `project`/`reconstruct` dispatches that routed
                   to a Pallas kernel in this context.
    interpret_calls : how many of those ran the kernel in interpret mode
                   (the plan's `interpret`; 0 on a TPU backend).
    force_depth  : nesting depth of active `force_pallas()` scopes; > 0
                   lets 'auto' pick the interpret-mode kernel off-TPU.
    breakdown    : per-(family, structure, route, order) dispatch counts,
                   covering EVERY dispatch — both routes, so the xla
                   fallbacks are visible too. `route` is the RESOLVED
                   backend ('pallas' | 'xla'), `structure` the input kind
                   ('dense' | 'tt' | 'cp' | 'sketch' | 'extern'). The
                   pre-existing fields stay bit-compatible: kernel_calls
                   always equals the sum of the route=='pallas' entries.
    """

    kernel_calls: int = 0
    interpret_calls: int = 0
    force_depth: int = 0
    breakdown: dict = dataclasses.field(default_factory=dict)

    @property
    def force_pallas(self) -> bool:
        return self.force_depth > 0

    def record(self, family: str, structure: str, route: str,
               order: int, *, interpret: bool) -> None:
        """Count one dispatch; pallas routes also bump `kernel_calls` (and
        `interpret_calls` when the kernel runs in interpret mode)."""
        key = (family, structure, route, order)
        self.breakdown[key] = self.breakdown.get(key, 0) + 1
        if route == "pallas":
            self.kernel_calls += 1
            self.interpret_calls += int(interpret)

    def breakdown_table(self) -> list[dict]:
        """The breakdown as sorted JSON-able rows (telemetry sinks)."""
        return [{"family": f, "structure": s, "route": r, "order": n,
                 "calls": c}
                for (f, s, r, n), c in sorted(self.breakdown.items())]


# The root stats is the default for code that never opens a dispatch_stats()
# scope; scopes (and anything run under contextvars.copy_context / asyncio
# tasks that set one) get their own isolated object.
_ROOT_STATS = DispatchStats()
_STATS: contextvars.ContextVar[DispatchStats] = contextvars.ContextVar(
    "repro_rp_dispatch_stats", default=_ROOT_STATS)


def current_stats() -> DispatchStats:
    """The `DispatchStats` object active in the current context."""
    return _STATS.get()


def kernel_call_count() -> int:
    """How many dispatches routed to a Pallas kernel in this context.

    Counts at dispatch (trace) time: under `jax.jit` a cached executable
    re-runs without re-dispatching, so this proves *routing*, not
    per-execution kernel launches.
    """
    return _STATS.get().kernel_calls


@contextlib.contextmanager
def dispatch_stats():
    """Install a fresh, isolated `DispatchStats` for the dynamic scope.

    Yields the object; counts and force-pallas state inside the scope never
    leak to (or read from) the enclosing context — use one per test when
    tests may run in parallel.
    """
    stats = DispatchStats()
    token = _STATS.set(stats)
    try:
        yield stats
    finally:
        _STATS.reset(token)


@contextlib.contextmanager
def force_pallas():
    """Let `backend='auto'` select the interpret-mode kernel off-TPU.

    Used by tests to exercise/prove the Pallas route on CPU; on real TPU
    hardware 'auto' selects the kernel by itself. Depth-counted on the
    context-local stats, so nested scopes compose and cannot clobber each
    other.
    """
    stats = _STATS.get()
    stats.force_depth += 1
    try:
        yield
    finally:
        stats.force_depth -= 1


def dispatch_breakdown() -> dict:
    """A copy of the current context's per-(family, structure, route,
    order) dispatch counts (see `DispatchStats.breakdown`)."""
    return dict(_STATS.get().breakdown)


def count_kernel_dispatch(family: str = "extern", structure: str = "extern",
                          order: int = 0, *, interpret: bool) -> None:
    """Record one Pallas kernel dispatch on the context-local stats.

    The public hook for kernel wrappers that live OUTSIDE the
    project/reconstruct dispatch matrix (e.g. the fused unsketch+EF+AdamW
    launch in `optim.adamw.update_sketched`) so `kernel_call_count()`
    stays the single source of truth for routing proofs. The optional tags
    place the launch in the per-(family, structure, route, order)
    `breakdown` (route is 'pallas' by definition here — this hook exists
    for kernel launches); untagged calls land under ('extern', 'extern',
    'pallas', 0), keeping the kernel_calls == sum-of-pallas-rows invariant.
    `interpret` is the launching plan's (`ExecutionPlan.interpret`).
    """
    _STATS.get().record(family, structure, "pallas", int(order),
                        interpret=interpret)


def _coerce_dense(op: RPOperator, x: jnp.ndarray) -> jnp.ndarray:
    """Reshape/pad a dense array to `(*batch, *op.in_dims)`.

    Accepts: exact `(*batch, *in_dims)` tensors; `(*batch, D)` flat vectors
    with D == prod(in_dims); any unbatched tensorization with the right
    element count; and `(*batch, D)` SHORT flat vectors with
    D < prod(in_dims), whose last axis is zero-padded up to prod(in_dims) —
    harmless under a linear map, and the batched case (e.g. a batch of
    ragged tail buckets) pads exactly like the 1-D case.

    Rejected with a typed error: trailing axes exceeding prod(in_dims)
    without matching `in_dims`, and NEAR-MISS tensors that match `in_dims`
    on every mode but the last — those are overwhelmingly truncated buckets
    (off-by-one slice bugs), not flat-vector batches, and padding them
    would silently project garbage.
    """
    dims = tuple(op.in_dims)
    n = len(dims)
    size = _prod(dims)
    x = jnp.asarray(x)
    if x.ndim >= n and tuple(x.shape[-n:]) == dims:
        return x
    if x.ndim >= 1 and x.shape[-1] == size:
        return x.reshape(x.shape[:-1] + dims)
    if x.ndim >= n and x.size == size:
        # alternate tensorization of a single input (e.g. a gradient bucket
        # shaped for a tensorized family, fed to a flat baseline); checked
        # BEFORE the short-vector branch so the total-size match keeps
        # meaning "one input", not "a batch of padded ones"
        return x.reshape(dims)
    if (x.ndim >= n and n > 1 and tuple(x.shape[-n:-1]) == dims[:-1]
            and x.shape[-1] != dims[-1]):
        # near-miss dense tensor: every mode but the last matches in_dims —
        # far more likely a truncated/over-long bucket (an off-by-one slice
        # bug) than a batch of flat vectors that happens to be stacked in
        # the operator's own mode sizes; refuse rather than pad garbage
        raise FormatMismatchError(
            f"dense input of shape {tuple(x.shape)} matches in_dims={dims} "
            f"on every mode but the last ({x.shape[-1]} != {dims[-1]}) — "
            "refusing to reinterpret a near-miss tensor as flat vectors")
    if x.ndim >= 1 and x.shape[-1] < size:
        # short flat vector(s): zero-pad the trailing axis, batched or not
        widths = [(0, 0)] * (x.ndim - 1) + [(0, size - x.shape[-1])]
        return jnp.pad(x, widths).reshape(x.shape[:-1] + dims)
    raise FormatMismatchError(
        f"dense input of shape {tuple(x.shape)} is incompatible with "
        f"operator in_dims={dims} (flat size {size})")


def _check_struct_dims(op: RPOperator, x) -> None:
    if tuple(x.dims) != tuple(op.in_dims):
        raise FormatMismatchError(
            f"{type(x).__name__} input dims {tuple(x.dims)} != operator "
            f"in_dims {tuple(op.in_dims)}")


def _run_planned(span_name: str, eplan, op, x) -> jnp.ndarray:
    """Record one dispatch on the context stats and execute the plan."""
    _STATS.get().record(eplan.family, eplan.structure, eplan.route,
                        eplan.order, interpret=eplan.interpret)
    with obs.span(span_name, family=eplan.family, structure=eplan.structure,
                  order=eplan.order, backend=eplan.route,
                  pipeline=eplan.pipeline, plan=eplan.plan_id):
        return _plan.execute_plan(eplan, op, x)


def _project_dense(op: RPOperator, x: jnp.ndarray, backend: str,
                   pipeline: str = "serial") -> jnp.ndarray:
    xt = _coerce_dense(op, x)
    eplan = _plan.plan_execution(op, _plan.dense_signature(op, xt),
                                 backend=backend, pipeline=pipeline)
    return _run_planned("rp.project", eplan, op, xt)


def _project_struct(op: RPOperator, x, backend: str,
                    pipeline: str = "serial") -> jnp.ndarray:
    """Structured (TT/CP-format) input(s), single or batched.

    TT/CP operators project in the compressed domain — the carry-sweep
    kernel subsystem (`repro.kernels.struct`) under the kernel policy, its
    batched einsum oracles otherwise; either way a batched container is ONE
    dispatch, never a vmap. Flat-vector families (gaussian/sparse)
    densify first — only viable at small prod(dims), which is exactly the
    regime the paper could run those baselines in.
    """
    if not isinstance(op, (TTRP, CPRP)):
        full = x.full()
        if isinstance(x, (BatchedTTTensor, BatchedCPTensor)):
            return _project_dense(op, full.reshape(full.shape[0], -1),
                                  backend, pipeline)
        return _project_dense(op, full.reshape(-1), backend, pipeline)
    _check_struct_dims(op, x)
    eplan = _plan.plan_execution(op, _plan.struct_signature(op, x),
                                 backend=backend, pipeline=pipeline)
    return _run_planned("rp.project", eplan, op, x)


def project(op: RPOperator, x, *, backend: str = "auto",
            pipeline: str = "serial") -> jnp.ndarray:
    """Project `x` with `op`, dispatching on the input's structure.

    x may be:
      * a dense array `(*batch, *op.in_dims)` — any operator order,
      * a flat vector or a `(*batch, D)` stack of them (auto-tensorized;
        short vectors are zero-padded, batched or not),
      * a `TTTensor` / `CPTensor` (compressed-domain fast path for
        tensorized families — never densified),
      * a `BatchedTTTensor` / `BatchedCPTensor` — a whole batch of
        structured inputs in ONE dispatch (the carry-sweep kernels put the
        batch on a native grid axis; there is no vmap on any route).

    `pipeline='double'` selects the double-buffered DMA schedule on the
    kernel routes (dense mode sweep and structured carry sweep) — same
    results to fp32 tolerance, input/core streams overlapped with the MXU
    contractions. Ignored on the einsum routes (there is nothing to
    pipeline by hand); validated either way so a typo cannot silently run
    serial.

    Returns the `(*batch, k)` sketch ((k,) for single structured inputs,
    (B, k) for batched containers).
    """
    _plan.validate_pipeline(pipeline)
    if isinstance(x, STRUCT_TYPES):
        return _project_struct(op, x, backend, pipeline)
    return _project_dense(op, x, backend, pipeline)


def reconstruct(op: RPOperator, y: jnp.ndarray, *, chunk: int | None = None,
                backend: str = "auto") -> jnp.ndarray:
    """Unbiased adjoint reconstruction, `(*batch, k) -> (*batch, *in_dims)`.

    A `(k,)` sketch returns an `in_dims`-shaped estimate (the original
    contract); batched sketches route to the batched mode-sweep adjoint
    kernels (`tt_reconstruct` / `cp_reconstruct`, any order
    N >= 2) under the same backend policy as `project` — ONE launch for the
    whole batch, no vmap — and otherwise fall back to a vmap of the
    operator's einsum adjoint.

    `chunk` is part of the resolved plan, not a warning: the einsum route
    honors it as the bound on the k-sized intermediate
    (`plan.chunk_policy == 'honored'`); the kernel route records
    `'folded'` — the planner's VMEM budget already tiles k, so the
    requested bound is honored by the kernel's own k-tiling and no dense
    (D, k) intermediate ever exists. Pass `backend='xla'` to make a
    specific chunk value authoritative; `rp.explain(op, y,
    kind='reconstruct', chunk=...)` shows the recorded policy.
    """
    y = jnp.asarray(y)
    if y.ndim < 1 or y.shape[-1] != op.k:
        raise FormatMismatchError(
            f"sketch shape {tuple(y.shape)} does not end in k = {op.k}")
    eplan = _plan.plan_execution(op, _plan.sketch_signature(op, y, chunk),
                                 kind="reconstruct", backend=backend)
    return _run_planned("rp.reconstruct", eplan, op, y)
