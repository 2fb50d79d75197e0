"""Operations and bytes of the projection work, counted from shapes.

These counts are the benchmark's own yardstick: a roofline share divides
the least time this work needs on the chip by the device time the trace
gives the kernels that did it. They never come from the program's plan
or from the compiler's cost analysis, so a change to either cannot move
them.

Each count is the contraction as the plain reference orders it (the
operator swept mode by mode against the input), two operations per
multiply-add. Bytes count the input read once, the result written once
and the operator read once per call, in float32.
"""
from __future__ import annotations

import dataclasses
import math

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)

    def least_seconds(self, peaks) -> tuple[float, str]:
        """The least time on a chip with `peaks`, and which peak bounds it."""
        t_flop = self.flops / peaks.flops
        t_byte = self.bytes / peaks.hbm_bw
        return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")


def operator_bytes(family, dims, k, rank) -> float:
    if family == "tt":
        ranks = [1] + [rank] * (len(dims) - 1) + [1]
        return F32 * k * sum(ranks[n] * d * ranks[n + 1]
                             for n, d in enumerate(dims))
    if family == "cp":
        return F32 * k * rank * sum(dims)
    raise ValueError(f"no work count for family {family!r}")


def _dense_flops(family, dims, k, rank) -> float:
    """Multiply-adds x2 of one dense item swept right to left."""
    n = len(dims)
    size = math.prod(dims)
    flops = 2.0 * size * k * rank                    # last mode, into (k, R)
    for m in range(n - 2, -1, -1):                   # modes m = N-2 .. 0
        lead = math.prod(dims[:m])
        inner = rank if (family == "tt" and m > 0) else 1
        flops += 2.0 * lead * k * rank * dims[m] * inner
    return flops


def project_dense(family, dims, k, rank, batch) -> Work:
    """`batch` dense items of shape `dims` to (batch, k) sketches."""
    size = math.prod(dims)
    return Work(batch * _dense_flops(family, dims, k, rank),
                F32 * batch * (size + k)
                + operator_bytes(family, dims, k, rank))


def reconstruct_dense(family, dims, k, rank, batch) -> Work:
    """The adjoint: (batch, k) sketches back to dense (batch, *dims)."""
    return project_dense(family, dims, k, rank, batch)


def project_struct(op_family, in_family, dims, k, rank, in_rank) -> Work:
    """One TT or CP item of rank `in_rank` through the carry sweep, without
    the operator read (count that once per call with `operator_bytes`)."""
    if op_family != "tt":
        raise ValueError(f"no carry-sweep count for operator {op_family!r}")
    r, q = rank, in_rank
    flops = 0.0
    for d in dims:
        if in_family == "tt":
            # carry (k, a, b) x core (k, a, d, s) -> (k, b, d, s), then
            # x input core (b, d, e) -> (k, s, e)
            flops += 2.0 * k * q * d * r * r + 2.0 * k * r * d * q * q
        elif in_family == "cp":
            flops += 2.0 * k * q * d * r * r + 2.0 * k * r * d * q
        else:
            raise ValueError(f"no carry-sweep count for input {in_family!r}")
    if in_family == "tt":
        ranks = [1] + [q] * (len(dims) - 1) + [1]
        in_elems = sum(ranks[n] * d * ranks[n + 1] for n, d in enumerate(dims))
    else:
        in_elems = q * sum(dims)
    return Work(flops, F32 * (in_elems + k))
