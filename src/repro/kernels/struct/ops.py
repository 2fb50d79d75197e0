"""Public wrappers around the carry-sweep kernels: layout + padding + jit.

`struct_project(op, x)` projects structured input(s) — `TTTensor`,
`CPTensor`, or their batched containers — with a TT or CP operator in ONE
kernel launch, covering all four (operator, input) family pairings at any
order 2..MAX_ORDER. The wrapper:

  * normalizes the input to a batched container (a single tensor becomes a
    B=1 batch; the batch axis is stripped again on return),
  * converts to the kernel layouts (squeezed TT boundary bonds on both the
    operator and the input; CP weights folded into the first factor — a
    scalar reweighting of one factor, exact by multilinearity),
  * plans the sweep (`plan.plan_carry_sweep`), lays every mode out as the
    kernel's pre-tiled slabs (`carry_operands`; zero padding of k, the
    batch and the bonds is inert and sliced away), and launches
    `carry.carry_sweep_project` with the fused 1/sqrt(k) epilogue.

With `use_kernel=False` (or for orders outside kernel support) the same
layouts run through the batched einsum oracles in `ref.py` — the XLA
reference path `rp.project(..., backend='xla')` uses for batched
structured inputs. Order-1 operators fall back to the dense path (a
1-core TT/CP "tensor" is its own densification).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core.cp_rp import CPRP
from repro.core.formats import (STRUCT_TYPES, BatchedCPTensor,
                                BatchedTTTensor, CPTensor, TTTensor)
from repro.core.tt_rp import TTRP

from ..ops import LANES, _pad_axis, kernel_order_supported, tt_cores_squeezed
from . import ref
from .carry import carry_sweep_project, carry_sweep_project_pipelined
from .plan import CarryPlan, plan_carry_sweep


def _as_batched(x):
    """-> (in_family, batched container, was_batched)."""
    if isinstance(x, TTTensor):
        return "tt", BatchedTTTensor(tuple(c[None] for c in x.cores)), False
    if isinstance(x, CPTensor):
        w = None if x.weights is None else x.weights[None]
        return "cp", BatchedCPTensor(tuple(f[None] for f in x.factors),
                                     w), False
    if isinstance(x, BatchedTTTensor):
        return "tt", x, True
    if isinstance(x, BatchedCPTensor):
        return "cp", x, True
    raise TypeError(f"not a structured input: {type(x).__name__}")


def _in_operands(in_family: str, xb) -> tuple[jnp.ndarray, ...]:
    """Kernel layout of the batched input: TT boundary bonds squeezed /
    CP weights folded into factor 0."""
    if in_family == "tt":
        cores = xb.cores
        if len(cores) == 1:
            return (cores[0][:, 0, :, 0],)
        return ((cores[0][:, 0, :, :],) + tuple(cores[1:-1])
                + (cores[-1][:, :, :, 0],))
    factors = xb.factors
    if xb.weights is not None:
        factors = (factors[0] * xb.weights[:, None, :],) + tuple(factors[1:])
    return factors


def struct_rank(x) -> int:
    """Structural rank of a (batched) TT/CP input: max bond rank for TT
    (interior bonds are what the carry holds), component count for CP."""
    if isinstance(x, (TTTensor, BatchedTTTensor)):
        return max(x.ranks)
    return x.rank


def _mode_tensors(family: str, cores, rank: int) -> list:
    """Per-mode `(rows, src, d, dst)` tensors of a squeezed core list — the
    first mode fans out from the unit bond, the last fans in — with every
    bond zero-padded to `rank`. An interior CP factor stays `(rows, d, R)`
    (its "diag" coupling)."""
    n = len(cores)
    out = []
    for m, c in enumerate(cores):
        if m == 0:
            t = c[:, None, :, :]
        elif m == n - 1:
            t = (c if family == "tt" else c.transpose(0, 2, 1))[..., None]
        elif family == "tt":
            t = c
        else:
            out.append(_pad_axis(c, 2, rank))
            continue
        t = _pad_axis(t, 1, rank) if m else t
        out.append(_pad_axis(t, 3, rank) if m < n - 1 else t)
    return out


def _op_slab(t: jnp.ndarray, tk: int) -> jnp.ndarray:
    """(K, src, d, dst) -> (K/TK, src, d, dst*TK); diag (K, d, R) ->
    (K/TK, 1, d, R*TK)."""
    t = _pad_axis(t, 0, tk)
    nk = t.shape[0] // tk
    if t.ndim == 3:
        t = t.reshape(nk, tk, *t.shape[1:]).transpose(0, 2, 3, 1)
        return t.reshape(nk, 1, t.shape[1], -1)
    t = t.reshape(nk, tk, *t.shape[1:]).transpose(0, 2, 3, 4, 1)
    return t.reshape(nk, t.shape[1], t.shape[2], -1)


def _in_slab(t: jnp.ndarray, tb: int) -> jnp.ndarray:
    """(B, src, d, dst) -> (B/TB, src, dst*TB, d); diag (B, d, R) ->
    (B/TB, 1, R*TB, d)."""
    t = _pad_axis(t.astype(jnp.float32), 0, tb)
    nb = t.shape[0] // tb
    if t.ndim == 3:
        t = t.reshape(nb, tb, *t.shape[1:]).transpose(0, 3, 1, 2)
        return t.reshape(nb, 1, -1, t.shape[-1])
    t = t.reshape(nb, tb, *t.shape[1:]).transpose(0, 2, 4, 1, 3)
    return t.reshape(nb, t.shape[1], -1, t.shape[-1])


def carry_operands(plan: CarryPlan, op_cores, in_cores) -> list:
    """The kernel's operands for `plan`: one operator slab per mode, then
    one input slab per mode (see `carry.py`)."""
    ops = [_op_slab(t, plan.tk) for t in
           _mode_tensors(plan.op_family, op_cores, plan.r_op)]
    ins = [_in_slab(t, plan.tb) for t in
           _mode_tensors(plan.in_family, in_cores, plan.r_in)]
    if plan.pipeline == "double":
        # the double-buffered input slots are sliced per DMA, which Mosaic
        # allows only on lane-aligned slabs: zero-pad each mode d (inert)
        ops = [_pad_axis(g, 2, LANES) for g in ops]
        ins = [_pad_axis(x, 3, LANES) for x in ins]
    return ops + ins


def struct_project(op, x, *, interpret: bool, use_kernel: bool = True,
                   pipeline: str = "serial") -> jnp.ndarray:
    """Project structured input(s) with a TT/CP operator, never densifying.

    x: TTTensor / CPTensor -> (k,); BatchedTTTensor / BatchedCPTensor with
    batch B -> (B, k) — ONE carry-sweep launch for the whole batch.
    `pipeline='double'` selects the double-buffered carry sweep
    (`carry.carry_sweep_project_pipelined`); same result.
    """
    if not isinstance(op, (TTRP, CPRP)):
        raise TypeError(f"struct_project needs a TT/CP operator, got "
                        f"{type(op).__name__}")
    op_family = "tt" if isinstance(op, TTRP) else "cp"
    in_family, xb, batched = _as_batched(x)
    if tuple(xb.dims) != tuple(op.in_dims):
        raise ValueError(f"input dims {tuple(xb.dims)} != operator in_dims "
                         f"{tuple(op.in_dims)}")
    k, b = op.k, xb.batch
    if op.order < 2:
        # a 1-core structured tensor IS dense; project it as such
        y = op.project(xb.full().reshape(b, *op.in_dims))
        return y if batched else y[0]
    op_cores = tt_cores_squeezed(op) if op_family == "tt" else op.factors
    in_cores = _in_operands(in_family, xb)
    ref_fn = ref.REFS[(op_family, in_family)]
    if not use_kernel or not kernel_order_supported(op.order):
        y = ref_fn(op_cores, in_cores) / jnp.sqrt(jnp.asarray(k, jnp.float32))
        return y if batched else y[0]
    plan = plan_carry_sweep(op_family, in_family, k, b, op.in_dims,
                            op.rank, struct_rank(xb), pipeline=pipeline)
    kernel = (carry_sweep_project_pipelined if plan.pipeline == "double"
              else carry_sweep_project)
    y = kernel(*carry_operands(plan, op_cores, in_cores),
               program=plan.program, tk=plan.tk, tb=plan.tb,
               scale=1.0 / math.sqrt(k), interpret=interpret)
    y = y[:b, :k]
    return y if batched else y[0]


__all__ = ["STRUCT_TYPES", "carry_operands", "struct_project",
           "struct_rank"]
