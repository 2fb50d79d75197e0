"""Contraction planner + jit'd public wrappers around the Pallas kernels.

The planner (`plan_contraction` -> `ContractionPlan`) is the single source
of truth for the order-N mode-sweep schedule: for a static order N it emits
the program of the sweep, the block-aligned, VMEM-budgeted tiles
`(tk, tb, ba)` and the grid, and the kernels in `_sweep.py` execute that
program verbatim inside a `pallas_call`: k-tile outermost for `project`
(cores stay VMEM-resident across the batch), k-tile innermost for
`reconstruct` (partial sums accumulate in the revisited output block), a
batch grid axis, and the JLT 1/sqrt(k) scaling fused into the epilogue.

Every program step is an operation the TPU's kernel compiler (Mosaic)
lowers directly. Inside the kernel the sketch row axis k sits on the 128
lanes, and a step is one of:

  ("dot", n, L)                       a 2-D MXU matmul with ONE contracting
                                      dimension, once per bond: project
                                      `(rows, L) @ (L, TK)`, reconstruct
                                      `(rows, TK) @ (TK, L)`;
  ("reduce", coupling, n_dst, n_src, d)
                                      broadcast-multiply by a `(d, TK)`
                                      core slice and sum over the d
                                      sublanes (project);
  ("expand", coupling, n_dst, n_src, d)
                                      its adjoint: broadcast-multiply (an
                                      outer product over d, reconstruct).

`coupling` is "full" (TT transfer cores: every input bond feeds every
output bond) or "diag" (CP factors: bond r feeds bond r only). Before
planning, trailing modes are merged while their product stays within
`MERGE_CAP` (`kernel_dims`): the merged core is formed once per call
outside the kernel, the first step becomes one wider matmul, and the sweep
intermediates shrink by the merged factor.

The wrappers (`tt_project` / `cp_project` and the adjoints `tt_reconstruct`
/ `cp_reconstruct`) lay the operator out for the program, pad batch and k,
and take a single input (`(*dims)` / `(k,)`) or a batch (`(B, *dims)` /
`(B, k)`) in ONE kernel launch; order-1 operators fall back to the jnp
reference path. `interpret` is required: the execution plan
(`repro.rp.plan`) decides it once, from the backend it resolved.

Structured (TT/CP-format) inputs route to `repro.kernels.struct`, whose
carry-sweep planner follows the same conventions.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.cp_rp import CPRP
from repro.core.formats import _prod
from repro.core.tt_rp import TTRP

from . import ref

# Per-kernel-instance VMEM the planners may account (blocks double-buffered
# by the Pallas pipeline plus the sweep temporaries), and the scoped-VMEM
# limit handed to Mosaic through the kernels' compiler parameters. A v5e
# TensorCore has 128 MiB of VMEM and Mosaic's default scoped limit is
# 16 MiB. Mosaic's own temporaries come on top of what the planner
# accounts: on a v5e chip a kernel planned at 40 MiB asked for 100.2 MiB
# of scoped VMEM, so the budget keeps that factor well inside the limit.
VMEM_BUDGET_BYTES = 24 * 1024 * 1024
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# Supported operator orders; 8 modes is far past the paper's N<=6 range.
MAX_ORDER = 8
# Trailing modes are merged while their product stays <= MERGE_CAP.
MERGE_CAP = 1024
# Cap on the unrolled elementwise bond updates of one kernel body: the
# bond loops are Python-unrolled, so huge ranks would explode the program.
MAX_UNROLLED_TERMS = 4096

# TPU block alignment: a block's last two dims are multiples of
# (SUBLANES, LANES) or equal to the whole array's.
SUBLANES, LANES = 8, 128

_FAMILIES = ("tt", "cp")
_KINDS = ("project", "reconstruct")
# 'serial': one streamed tile per grid step (Pallas-managed copies).
# 'double': the d1 axis moves inside the kernel and the streamed operands
# are double-buffered by explicit DMAs (project only) — the second VMEM
# slot is accounted by the planner.
PIPELINES = ("serial", "double")


class KernelPlanError(ValueError):
    """The kernel cannot be planned within the TPU's limits (VMEM at the
    aligned tile floor, or too many unrolled bond terms). The execution
    plan records it as the kernel route's rejection reason and the 'auto'
    policy takes the einsum route."""


def validate_pipeline(pipeline: str) -> str:
    """The single `pipeline=` check (every layer — planners, dispatch,
    `rp.plan_execution` — delegates here): returns it, or raises the one
    typed ValueError naming the accepted set. Survives `python -O`."""
    if pipeline not in PIPELINES:
        raise ValueError(f"unknown pipeline {pipeline!r}; expected "
                         f"{PIPELINES}")
    return pipeline


def _pad_axis(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = a.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _lane_tile(k: int) -> int:
    """k tile: the 128-lane width, or all of a smaller k (padded to a
    power of two, so the block equals the whole padded axis)."""
    return LANES if k >= LANES else max(SUBLANES, 1 << (k - 1).bit_length())


def _batch_tile(b: int, cap: int) -> int:
    """Batch tile on the sublane axis: a multiple of 8 up to `cap`, or all
    of a batch of at most 8 (padded to 8)."""
    tb = SUBLANES
    while tb * 2 <= min(cap, max(b, SUBLANES)):
        tb *= 2
    return tb


def kernel_dims(dims: tuple[int, ...]) -> tuple[int, ...]:
    """The mode sizes the kernels sweep: trailing modes merged while their
    product stays <= MERGE_CAP; the leading mode is never merged."""
    dims = tuple(int(d) for d in dims)
    last = len(dims) - 1
    while last > 1 and dims[last - 1] * _prod(dims[last:]) <= MERGE_CAP:
        last -= 1
    return dims[:last] + (_prod(dims[last:]),)


# ---------------------------------------------------------------------------
# mode-sweep programs
# ---------------------------------------------------------------------------

def _bond_steps(family: str, rank: int, kdims: tuple[int, ...]):
    """(coupling, n_dst, n_src, d) of every non-dot step, in project order
    (trailing interior mode first, leading mode last)."""
    r = rank
    steps = [("full" if family == "tt" else "diag",
              r, r if family == "tt" else 1, d) for d in kdims[-2:0:-1]]
    steps.append(("full", 1, r, kdims[0]))
    return steps


def _project_steps(family: str, rank: int, kdims: tuple[int, ...]) -> tuple:
    """Program of the projection sweep, merged trailing mode first: one
    matmul per bond against the (merged) last core, then one reduce per
    remaining mode, down to the leading mode's `(TB, TK)` output tile."""
    return ((("dot", rank, kdims[-1]),)
            + tuple(("reduce",) + s for s in _bond_steps(family, rank,
                                                         kdims)))


def _reconstruct_steps(family: str, rank: int,
                       kdims: tuple[int, ...]) -> tuple:
    """Program of the adjoint: the projection program reversed — expand
    the `(TB, TK)` sketch tile through the leading and interior cores,
    then one matmul per bond against the (merged) last core, accumulated
    into the `(TB, BA*Q, L)` output block over the k grid axis."""
    return (tuple(("expand",) + s
                  for s in reversed(_bond_steps(family, rank, kdims)))
            + (("dot", rank, kdims[-1]),))


def _unrolled_terms(steps) -> int:
    return sum(s[1] if s[0] == "dot" else
               (s[2] * s[3] if s[1] == "full" else s[2])
               for s in steps)


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ContractionPlan:
    """A fully-resolved mode-sweep schedule for one kernel launch.

    `steps` is the program (`_project_steps` / `_reconstruct_steps`) the
    sweep kernels execute verbatim — static (a tuple of tuples), so it
    participates in the jit cache key. `kdims` are the swept mode sizes
    after trailing-mode merging. `vmem_bytes` is the accounted
    per-instance footprint at the chosen tiles.
    """

    family: str
    kind: str
    k: int
    b: int
    dims: tuple[int, ...]
    kdims: tuple[int, ...]
    rank: int
    tk: int
    tb: int
    ba: int
    steps: tuple
    vmem_bytes: int
    pipeline: str = "serial"

    @property
    def order(self) -> int:
        return len(self.dims)

    @property
    def q(self) -> int:
        """Rows per leading-mode index: the product of the interior kernel
        modes (1 when the kernel sweeps two modes)."""
        return _prod(self.kdims[1:-1])

    @property
    def grid(self) -> tuple[int, ...]:
        """Grid for the padded problem (k-tile outermost for project,
        innermost for reconstruct). Under pipeline='double' the project d1
        axis moves inside the kernel, so the launch grid is (nk, nb)."""
        nk = -(-self.k // self.tk)
        nb = -(-self.b // self.tb)
        na = -(-self.dims[0] // self.ba)
        if self.kind == "project":
            if self.pipeline == "double":
                return (nk, nb)
            return (nk, nb, na)
        return (nb, na, nk)


def _ba_candidates(d1: int, q: int) -> list[int]:
    """Leading-mode tiles, largest first: d1 itself, then the divisors of
    d1 that keep the `(TB, BA*Q, L)` input block and its in-kernel
    `(TB*BA*Q, L)` collapse sublane-aligned — multiples of 8, and 1 (the
    leading core's block `(BA, R, TK)` puts BA on a leading axis)."""
    out = [d1]
    for ba in range(d1 - 1, 0, -1):
        if d1 % ba == 0 and (ba % SUBLANES == 0 or ba == 1) \
                and (ba * q) % SUBLANES == 0:
            out.append(ba)
    return out


def plan_contraction(family: str, kind: str, k: int, b: int,
                     dims: tuple[int, ...], rank: int, *,
                     budget: int = VMEM_BUDGET_BYTES,
                     pipeline: str = "serial",
                     dense_blocks: int = 0) -> ContractionPlan:
    """Plan a mode-sweep kernel launch for static order N = len(dims).

    Tiles start at the TPU floor — TK the 128-lane width (or all of a
    smaller k), TB eight sublanes (or all of a batch of at most eight) —
    and the leading-mode tile BA shrinks through the aligned divisors of
    d1 until the accounted footprint fits `budget`: every streamed block
    twice (the Pallas pipeline double-buffers), the sweep's widest
    intermediate (all bond slabs plus one product temporary), and
    `dense_blocks` extra `(TB, BA*Q, L)` blocks for a fused epilogue.
    Raises `KernelPlanError` when nothing fits.
    """
    if kind not in _KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected {_KINDS}")
    if family not in _FAMILIES:
        raise ValueError(f"unknown family {family!r}; expected {_FAMILIES}")
    validate_pipeline(pipeline)
    if pipeline == "double" and kind != "project":
        raise ValueError(
            "pipeline='double' is implemented for kind='project' only: the "
            "reconstruct sweep accumulates over the k grid axis in the "
            "revisited output block and stays serial")
    dims = tuple(int(d) for d in dims)
    order = len(dims)
    if order < 2:
        raise ValueError(f"mode-sweep kernels need order >= 2, got dims={dims}")
    if order > MAX_ORDER:
        raise ValueError(f"order {order} exceeds MAX_ORDER={MAX_ORDER}")
    r = max(1, int(rank))
    kdims = kernel_dims(dims)
    d1, q, ell = kdims[0], _prod(kdims[1:-1]), kdims[-1]
    steps = (_project_steps(family, r, kdims) if kind == "project"
             else _reconstruct_steps(family, r, kdims))
    terms = _unrolled_terms(steps)
    if terms > MAX_UNROLLED_TERMS:
        raise KernelPlanError(
            f"plan_contraction: rank {r} unrolls {terms} bond terms per "
            f"kernel body (> {MAX_UNROLLED_TERMS})")
    tk = _lane_tile(k)
    tb = _batch_tile(b, SUBLANES)
    interior = sum(n_dst * n_src * d for _, n_dst, n_src, d
                   in _bond_steps(family, r, kdims)[:-1])

    def footprint(ba: int) -> int:
        rows = tb * ba * q
        x_blk = rows * ell
        cores = tk * (r * ell + interior + max(r, SUBLANES) * ba)
        streamed = x_blk + cores + tb * tk + dense_blocks * x_blk
        if pipeline == "double":
            streamed += x_blk + tk * r * ba   # explicit second slots
        temps = (r + 1) * rows * tk + (rows * ell if kind == "reconstruct"
                                       else 0)
        return 4 * (2 * streamed + temps)

    for ba in _ba_candidates(d1, q):
        if footprint(ba) <= budget:
            break
    else:
        raise KernelPlanError(
            f"plan_contraction(kind={kind!r}): dims={dims} (swept as "
            f"{kdims}), rank={r} need {footprint(ba)} bytes of VMEM at the "
            f"aligned tile floor (tk={tk}, tb={tb}, ba={ba}) > budget "
            f"{budget}")
    return ContractionPlan(family=family, kind=kind, k=k, b=b, dims=dims,
                           kdims=kdims, rank=r, tk=tk, tb=tb, ba=ba,
                           steps=steps, vmem_bytes=footprint(ba),
                           pipeline=pipeline)


def sweep_hbm_bytes(plan: ContractionPlan) -> int:
    """Grid-accurate analytic HBM traffic of ONE batched sweep launch.

    Follows the BlockSpec index maps laid out in `_sweep.py`: a block is
    re-fetched whenever its index map changes between consecutive grid
    steps and stays resident otherwise. The SAME traffic applies to the
    serial and double-buffered project schedules — pipelining overlaps the
    transfers with compute, it does not remove bytes — so timing rows,
    rooflines, and the fused-update accounting all read this one function.
    """
    k, b, r = plan.k, plan.b, plan.rank
    kd = plan.kdims
    nk = -(-k // plan.tk)
    nb_t = -(-b // plan.tb)
    na = -(-kd[0] // plan.ba)
    x_total = 4 * b * _prod(plan.dims)
    y_total = 4 * b * k
    c1 = 4 * k * kd[0] * r                 # leading core, d1-tile indexed
    c_rest = 4 * k * (r * kd[-1] + sum(
        n_dst * n_src * d for _, n_dst, n_src, d
        in _bond_steps(plan.family, r, kd)[:-1]))
    if plan.kind == "project":
        # grid (ik, ib[, ia]): x re-streamed once per k-tile; the d1-tiled
        # leading core once per batch tile; the other cores once per k-tile
        return nk * x_total + nb_t * c1 + c_rest + y_total
    # grid (ib, ia, ik): y re-fetched once per d1-tile; leading core once
    # per batch tile; the other cores re-streamed per (batch, d1) tile
    return na * y_total + nb_t * c1 + nb_t * na * c_rest + x_total


def pick_tiles(k: int, b: int, dims: tuple[int, ...], rank: int, *,
               kind: str = "project", family: str = "tt",
               budget: int = VMEM_BUDGET_BYTES) -> tuple[int, int, int]:
    """Block-aligned, VMEM-budgeted (tk, tb, ba) for an order-N batched
    kernel — the tile view of `plan_contraction`."""
    plan = plan_contraction(family, kind, k, b, dims, rank, budget=budget)
    return plan.tk, plan.tb, plan.ba


# ---------------------------------------------------------------------------
# operator-container layouts
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def tt_cores_squeezed(op: TTRP) -> tuple[jnp.ndarray, ...]:
    """TT cores with the boundary bonds (r_0 = r_N = 1) squeezed —
    (k, d1, R), interior (k, R, dn, R), (k, R, dN). Requires order >= 2."""
    cores = op.cores
    return ((cores[0][:, 0, :, :],) + tuple(cores[1:-1])
            + (cores[-1][:, :, :, 0],))


def _merged_last(family: str, cores, n_merge: int) -> jnp.ndarray:
    """The last `n_merge` cores contracted into one: TT (k, R, L), CP
    (k, L, R) — formed outside the kernel, at full float32 precision."""
    g = cores[-1]
    for c in reversed(cores[len(cores) - n_merge:-1]):
        if family == "tt":
            g = jnp.einsum("kudv,kvl->kudl", c, g, precision=HIGHEST)
            g = g.reshape(g.shape[0], g.shape[1], -1)
        else:
            g = (c[:, :, None, :] * g[:, None, :, :]).reshape(
                c.shape[0], -1, c.shape[2])
    return g


def sweep_operands(family: str, cores, plan: ContractionPlan) -> list:
    """The operator laid out for `plan.steps`, one array per step, k last
    (on the lanes) and zero-padded to the k tile:

      dot      project (R, L, K) / reconstruct (R, K, L) — the merged
               last core;
      reduce / expand  (n_dst, n_src, d, K) — TT core g[k, u, d, v] as
               [u, v, d, k]; CP factor f[k, d, r] as [r, 0, d, k];
      leading  (d1, R, K) — core/factor [k, a, u] as [a, u, k], so the
               d1 tile is a leading block axis and may be 1.
    """
    kd = plan.kdims
    n_merge = len(plan.dims) - len(kd) + 1
    last = _merged_last(family, cores, n_merge)
    if plan.kind == "project":
        dot = last.transpose((1, 2, 0) if family == "tt" else (2, 1, 0))
    else:
        dot = last.transpose((1, 0, 2) if family == "tt" else (2, 0, 1))
    interior = []
    for c in cores[1:len(kd) - 1]:
        interior.append(c.transpose(1, 3, 2, 0) if family == "tt"
                        else c.transpose(2, 1, 0)[:, None])
    lead = _pad_axis(cores[0].transpose(1, 2, 0), 2, plan.tk)
    k_axis = 1 if plan.kind == "reconstruct" else 2
    dot = _pad_axis(dot, k_axis, plan.tk)
    ws = [_pad_axis(w, 3, plan.tk) for w in interior[::-1]] + [lead]
    if plan.kind == "project":
        return [dot] + ws
    return ws[::-1] + [dot]


def _as_batch(x: jnp.ndarray, ndim: int) -> tuple[jnp.ndarray, bool]:
    """Add a singleton batch axis when `x` is a single input of rank `ndim`."""
    if x.ndim == ndim:
        return x[None], False
    if x.ndim != ndim + 1:
        raise ValueError(f"expected a rank-{ndim} input or a batch of them, "
                         f"got shape {x.shape}")
    return x, True


def dense_blocks_view(plan: ContractionPlan, a: jnp.ndarray) -> jnp.ndarray:
    """(B, *dims) -> the kernels' (B padded to TB, d1*Q, L) float32 view."""
    a = a.astype(jnp.float32).reshape(a.shape[0], plan.kdims[0] * plan.q,
                                      plan.kdims[-1])
    return _pad_axis(a, 0, plan.tb)


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def _sweep_project(family, op, cores, x, interpret, pipeline):
    from ._sweep import sweep_project, sweep_project_pipelined
    xb, batched = _as_batch(x, op.order)
    plan = plan_contraction(family, "project", op.k, xb.shape[0],
                            op.in_dims, op.rank, pipeline=pipeline)
    kern = (sweep_project_pipelined if plan.pipeline == "double"
            else sweep_project)
    y = kern(dense_blocks_view(plan, xb), *sweep_operands(family, cores, plan),
             steps=plan.steps, tk=plan.tk, tb=plan.tb, ba=plan.ba,
             scale=1.0 / math.sqrt(op.k), interpret=interpret)
    y = y[:xb.shape[0], :op.k]
    return y if batched else y[0]


def kernel_order_supported(order: int) -> bool:
    """Orders the mode-sweep kernels cover; outside it (order-1 classical
    Gaussian, order > MAX_ORDER) the wrappers fall back to einsum."""
    return 2 <= order <= MAX_ORDER


def tt_project(op: TTRP, x: jnp.ndarray, *, interpret: bool,
               use_kernel: bool = True,
               pipeline: str = "serial") -> jnp.ndarray:
    """f_TT(R)(x) for dense order-N input(s) via the mode-sweep kernel.

    x: (*dims) -> (k,)  or  (B, *dims) -> (B, k), one launch either way.
    `pipeline='double'` selects the double-buffered DMA schedule
    (`sweep_project_pipelined`) — same result, overlapped streams.
    """
    if not kernel_order_supported(op.order) or not use_kernel:
        return op.project(x)
    return _sweep_project("tt", op, tt_cores_squeezed(op), x, interpret,
                          pipeline)


def cp_project(op: CPRP, x: jnp.ndarray, *, interpret: bool,
               use_kernel: bool = True,
               pipeline: str = "serial") -> jnp.ndarray:
    """f_CP(R)(x) for dense order-N input(s) via the mode-sweep kernel."""
    if not kernel_order_supported(op.order) or not use_kernel:
        return op.project(x)
    return _sweep_project("cp", op, op.factors, x, interpret, pipeline)


# ---------------------------------------------------------------------------
# adjoints
# ---------------------------------------------------------------------------

def _sweep_reconstruct(family, op, cores, y, interpret):
    from ._sweep import sweep_reconstruct
    yb, batched = _as_batch(y, 1)
    plan = plan_contraction(family, "reconstruct", op.k, yb.shape[0],
                            op.in_dims, op.rank)
    yk = _pad_axis(_pad_axis(yb.astype(jnp.float32), 0, plan.tb), 1, plan.tk)
    out = sweep_reconstruct(yk, *sweep_operands(family, cores, plan),
                            steps=plan.steps, tk=plan.tk, tb=plan.tb,
                            ba=plan.ba, scale=1.0 / math.sqrt(op.k),
                            interpret=interpret)
    out = out[:yb.shape[0]].reshape((yb.shape[0],) + tuple(op.in_dims))
    return out if batched else out[0]


def tt_reconstruct(op: TTRP, y: jnp.ndarray, *, interpret: bool,
                   use_kernel: bool = True) -> jnp.ndarray:
    """Unbiased adjoint for sketch(es): (k,) -> dims or (B,k) -> (B,*dims).

    Batched sketches reconstruct in ONE launch; padding k with zero sketch
    entries keeps padded core rows inert (y multiplies every term).
    """
    if not kernel_order_supported(op.order) or not use_kernel:
        if y.ndim == 2:
            return jax.vmap(op.reconstruct)(y)
        return op.reconstruct(y)
    return _sweep_reconstruct("tt", op, tt_cores_squeezed(op), y, interpret)


def cp_reconstruct(op: CPRP, y: jnp.ndarray, *, interpret: bool,
                   use_kernel: bool = True) -> jnp.ndarray:
    """Unbiased adjoint for sketch(es) of a CP operator; see tt_reconstruct."""
    if not kernel_order_supported(op.order) or not use_kernel:
        if y.ndim == 2:
            return jax.vmap(op.reconstruct)(y)
        return op.reconstruct(y)
    return _sweep_reconstruct("cp", op, op.factors, y, interpret)


__all__ = ["ContractionPlan", "KernelPlanError", "MAX_ORDER", "PIPELINES",
           "VMEM_BUDGET_BYTES", "VMEM_LIMIT_BYTES", "cp_project",
           "cp_reconstruct", "kernel_dims", "kernel_order_supported",
           "pick_tiles", "plan_contraction", "ref", "sweep_hbm_bytes",
           "sweep_operands", "tt_cores_squeezed", "tt_project",
           "tt_reconstruct", "validate_pipeline"]
