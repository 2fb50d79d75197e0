"""Carry-sweep contraction planner for structured (TT/CP-format) inputs.

This is the structured-input counterpart of `repro.kernels.ops.plan_contraction`
(which plans the DENSE-input mode sweep): instead of streaming a dense
`(B, d1..dN)` block and peeling one mode per step, the carry sweep contracts
one mode of the OPERATOR against the same mode of the INPUT's compressed
representation, carrying a small `(R_op, R_in, TB, TK)` bond state between
modes — the paper's "project without ever densifying" formulation
(Sec. 4.1; Feng et al.'s TT-input carry sweep; Iwen et al.'s modewise maps
on compressed inputs). Cost is O(k N d R R~ (R + R~)) per item instead of
the dense path's O(k R d^N) (`repro.core.theory.flops_project_struct`).

All FOUR pairings share one program shape, one step per mode, emitted by
`_carry_program` for any static order 2 <= N <= `MAX_ORDER`:

  ("mode", op_coupling, op_src, op_dst, in_coupling, in_src, in_dst)

A TT core couples every incoming bond to every outgoing one ("full"); an
interior CP factor keeps component r on component r ("diag"); the first
mode fans the unit bond out and the last fans in to it, for both families.
Inside the kernel the batch sits on the sublanes and k on the lanes, and a
mode update is one 2-D MXU matmul per (operator source bond, input source
bond) — the input slab `(in_dst*TB, d)` against the operator slab
`(d, op_dst*TK)`, contracting the mode d — followed by aligned block
slices and elementwise products with the carry (a Hadamard on the bond for
"diag"). So every step is a matmul with one contracting dimension or
elementwise work.

`plan_carry_sweep` budgets VMEM — operator slabs per k-tile and input slabs
per batch tile (both double-buffered by the Pallas pipeline), the carry,
and the matmul result — and shrinks the batch tile (eight sublanes at the
floor) until it fits, raising `KernelPlanError` when it cannot, or when the
ranks would unroll more than `MAX_UNROLLED_TERMS` bond updates.
"""
from __future__ import annotations

import dataclasses

from ..ops import (MAX_ORDER, MAX_UNROLLED_TERMS, SUBLANES,
                   VMEM_BUDGET_BYTES, KernelPlanError, _batch_tile,
                   _lane_tile, validate_pipeline)

_FAMILIES = ("tt", "cp")


def _require_family(name: str, value: str) -> None:
    if value not in _FAMILIES:
        raise ValueError(f"unknown {name} {value!r}; expected {_FAMILIES}")


def _bonds(family: str, n: int, order: int, rank: int) -> tuple:
    """(coupling, src, dst) of `family`'s core at mode n."""
    if n == 0:
        return ("full", 1, rank)
    if n == order - 1:
        return ("full", rank, 1)
    return ("full" if family == "tt" else "diag", rank, rank)


def _carry_program(op_family: str, in_family: str, order: int,
                   r_op: int, r_in: int) -> tuple:
    """The carry program for one (operator, input) family pairing: one
    ("mode", op_coupling, op_src, op_dst, in_coupling, in_src, in_dst)
    step per mode, first mode first."""
    _require_family("operator family", op_family)
    _require_family("input family", in_family)
    if not 2 <= order <= MAX_ORDER:
        raise ValueError(
            f"carry-sweep kernels need 2 <= order <= {MAX_ORDER}, "
            f"got {order}")
    return tuple(("mode",) + _bonds(op_family, n, order, r_op)
                 + _bonds(in_family, n, order, r_in) for n in range(order))


def _unrolled_terms(program) -> int:
    """Elementwise bond updates the kernel body unrolls."""
    return sum((s[2] if s[1] == "full" else 1)
               * (s[5] if s[4] == "full" else 1) * s[3] for s in program)


@dataclasses.dataclass(frozen=True)
class CarryPlan:
    """A fully-resolved carry-sweep schedule for one structured launch.

    `program` is the static step tuple (`_carry_program`) the kernel in
    `carry.py` executes verbatim. `vmem_bytes` is the accounted
    per-instance footprint at the chosen `(tk, tb)` tiles.
    """

    op_family: str
    in_family: str
    k: int
    b: int
    dims: tuple[int, ...]
    r_op: int
    r_in: int
    tk: int
    tb: int
    program: tuple
    vmem_bytes: int
    pipeline: str = "serial"

    @property
    def order(self) -> int:
        return len(self.dims)

    @property
    def grid(self) -> tuple[int, ...]:
        """Grid for the padded problem: k-tile OUTERMOST (the operator
        cores — indexed only by ik — stay VMEM-resident while the whole
        batch of structured inputs streams through), batch tile inner.
        Under pipeline='double' the batch axis moves inside the kernel
        (double-buffered input-core tiles), so the launch grid is (nk,)."""
        nk = -(-self.k // self.tk)
        if self.pipeline == "double":
            return (nk,)
        return (nk, -(-self.b // self.tb))

    @property
    def carry_bytes(self) -> int:
        """Peak bytes of the carried bond state for the FULL problem —
        b * k * R_op * R_in floats, the `(R_op, R_in, B, k)` carry that
        replaces the dense path's (B, k, d2..dN) sweep intermediates."""
        return 4 * self.b * self.k * self.r_op * self.r_in


def _core_elems(family: str, dims: tuple[int, ...], rank: int) -> int:
    """Per-row (k or batch) element count of a squeezed core/factor list."""
    if family == "tt":
        if len(dims) == 1:
            return dims[0]
        return (dims[0] * rank + sum(rank * d * rank for d in dims[1:-1])
                + rank * dims[-1])
    return rank * sum(dims)


def plan_carry_sweep(op_family: str, in_family: str, k: int, b: int,
                     dims: tuple[int, ...], r_op: int, r_in: int, *,
                     budget: int = VMEM_BUDGET_BYTES,
                     pipeline: str = "serial") -> CarryPlan:
    """Plan a carry-sweep kernel launch for static order N = len(dims).

    TK is the 128-lane width (or all of a smaller k); the batch tile starts
    at 128 sublanes and halves until the footprint fits `budget`, down to
    the eight-sublane floor (or all of a batch of at most eight). The
    footprint counts the operator slabs `(op_src, d, op_dst*TK)` and the
    input slabs `(in_src, in_dst*TB, d)` of every mode twice (Pallas
    double-buffers streamed blocks), the old and new carry, the widest
    matmul result, and the `(TB, TK)` output block.

    `pipeline='double'` (the double-buffered kernel) accounts its explicit
    second input slot and the full-batch `(B, TK)` output block the
    in-kernel batch sweep writes through.
    """
    dims = tuple(int(d) for d in dims)
    validate_pipeline(pipeline)
    r_op, r_in = max(1, int(r_op)), max(1, int(r_in))
    program = _carry_program(op_family, in_family, len(dims), r_op, r_in)
    terms = _unrolled_terms(program)
    if terms > MAX_UNROLLED_TERMS:
        raise KernelPlanError(
            f"plan_carry_sweep: ranks ({r_op}, {r_in}) unroll {terms} bond "
            f"updates per kernel body (> {MAX_UNROLLED_TERMS})")
    tk = _lane_tile(k)

    def slab_elems(tb: int):
        op = inp = widest = 0
        for (_, oc, o_src, o_dst, ic, i_src, i_dst), d in zip(program, dims):
            o_loops = o_src if oc == "full" else 1
            i_loops = i_src if ic == "full" else 1
            op += o_loops * d * o_dst * tk
            inp += i_loops * i_dst * tb * d
            widest = max(widest, i_dst * tb * o_dst * tk)
        return op, inp, widest

    def footprint(tb: int) -> int:
        op, inp, widest = slab_elems(tb)
        carry = 2 * r_op * r_in * tb * tk
        if pipeline == "double":
            out = -(-b // tb) * tb * tk
            extra = inp
        else:
            out = tb * tk
            extra = 0
        return 4 * (2 * (op + inp + out) + extra + carry + widest)

    tb = _batch_tile(b, 128)
    while footprint(tb) > budget and tb > SUBLANES:
        tb //= 2
    if footprint(tb) > budget:
        raise KernelPlanError(
            f"plan_carry_sweep: dims={dims}, ranks ({r_op}, {r_in}) need "
            f"{footprint(tb)} bytes of VMEM at the aligned tile floor "
            f"(tk={tk}, tb={tb}) > budget {budget}")
    return CarryPlan(op_family=op_family, in_family=in_family, k=k, b=b,
                     dims=dims, r_op=r_op, r_in=r_in, tk=tk, tb=tb,
                     program=program, vmem_bytes=footprint(tb),
                     pipeline=pipeline)


def struct_hbm_bytes(plan: CarryPlan) -> int:
    """Grid-accurate analytic HBM traffic of one carry-sweep launch.

    Follows the BlockSpec index maps in `carry.py`: operator cores are
    indexed only by the outermost k-tile axis (fetched once each), input
    cores by the batch axis (re-streamed once per k-tile), and each
    `(TB, TK)` output block is written exactly once.
    """
    nk = -(-plan.k // plan.tk)
    op_bytes = 4 * plan.k * _core_elems(plan.op_family, plan.dims, plan.r_op)
    in_bytes = 4 * plan.b * _core_elems(plan.in_family, plan.dims, plan.r_in)
    out_bytes = 4 * plan.b * plan.k
    return op_bytes + nk * in_bytes + out_bytes


__all__ = ["CarryPlan", "plan_carry_sweep", "struct_hbm_bytes"]
