"""Pallas TPU kernel: batched structured-input carry-sweep projection.

Executes the carry program emitted by `plan.plan_carry_sweep` verbatim, for
all four (operator, input) family pairings at any static order
2..MAX_ORDER.

Schedule:

* grid = (k/TK, B/TB) — k-tile OUTERMOST: the operator slabs' block index
  depends only on ik, so one k-tile's operator is fetched once and stays
  VMEM-resident while every batch tile of structured inputs streams
  through it. The input slabs' index depends only on ib.
* No accumulation axis: every mode is contracted in full inside the
  instance, carrying the `(R_op, R_in, TB, TK)` bond state between modes,
  so each (TB, TK) output block is written exactly once.
* The batch sits on the sublanes and k on the lanes. A mode update is one
  2-D MXU matmul per (operator source bond, input source bond) — input
  slab `(in_dst*TB, d)` @ operator slab `(d, op_dst*TK)` — then aligned
  block slices of the result multiply the carry elementwise. The JLT
  1/sqrt(k) scaling is FUSED into the epilogue.

Operand layouts (built by `ops.struct_project`, see `carry_operands`): the
operator at mode n as `(K/TK, op_src, d, op_dst*TK)`, the input as
`(B/TB, in_src, in_dst*TB, d)` — both pre-tiled along their streamed axis,
so every block is whole in its last two dims. A "diag" coupling (an
interior CP factor) stacks all components into one slab with source count
1. Zero padding of k, the batch, and the bonds is inert.

`carry_sweep_project_pipelined` is the DOUBLE-BUFFERED variant (plan
`pipeline='double'`): grid = (k/TK,) with the batch axis swept by an
in-kernel fori_loop — the per-batch-tile input slabs are prefetched into a
second VMEM slot with explicit `pltpu.make_async_copy` DMAs while the
current batch tile's carry program runs, and the `(B, TK)` output block is
written one batch tile at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._sweep import compiler_params, dot


def carry_body(program, g_load, x_load, tb: int, tk: int):
    """Run the carry program; `g_load(n, u)` is the `(d, op_dst*TK)`
    operator slab of source bond u at mode n, `x_load(n, e)` the
    `(in_dst*TB, d)` input slab of source bond e. Returns `(TB, TK)`."""
    carry = None                       # carry[v]: (R_in, TB, TK)
    for n, (_, oc, o_src, o_dst, ic, i_src, i_dst) in enumerate(program):
        new = [None] * o_dst
        for u in range(o_src if oc == "full" else 1):
            g = g_load(n, u)
            for e in range(i_src if ic == "full" else 1):
                p = dot(x_load(n, e), g).reshape(i_dst, tb, o_dst * tk)
                for v in range(o_dst):
                    term = p[:, :, v * tk:(v + 1) * tk]
                    if carry is not None:
                        c = carry[u if oc == "full" else v]
                        term = term * (c[e][None] if ic == "full" else c)
                    new[v] = term if new[v] is None else new[v] + term
        carry = new
    return carry[0][0]


def _carry_kernel(*refs, program, scale, tb, tk):
    n = len(program)
    g_refs, x_refs, o_ref = refs[:n], refs[n:2 * n], refs[-1]
    y = carry_body(program, lambda m, u: g_refs[m][0, u],
                   lambda m, e: x_refs[m][0, e], tb, tk)
    o_ref[...] = y * scale


@functools.partial(jax.jit, static_argnames=("program", "tk", "tb",
                                             "scale", "interpret"))
def carry_sweep_project(*operands: jnp.ndarray, program, tk: int, tb: int,
                        scale: float, interpret: bool) -> jnp.ndarray:
    """ONE launch projecting a whole batch of structured inputs.

    operands = (*op_slabs, *in_slabs), one of each per mode (see the module
    docstring). Returns the (B, K) float32 sketch, B and K padded.
    """
    n = len(program)
    g, x = operands[:n], operands[n:]
    nk, nb = g[0].shape[0], x[0].shape[0]
    in_specs = [pl.BlockSpec((1,) + a.shape[1:],
                             lambda ik, ib: (ik, 0, 0, 0)) for a in g]
    in_specs += [pl.BlockSpec((1,) + a.shape[1:],
                              lambda ik, ib: (ib, 0, 0, 0)) for a in x]
    return pl.pallas_call(
        functools.partial(_carry_kernel, program=program, scale=scale,
                          tb=tb, tk=tk),
        name="carry_sweep_project",
        grid=(nk, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, tk), lambda ik, ib: (ib, ik)),
        out_shape=jax.ShapeDtypeStruct((nb * tb, nk * tk), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)


def _carry_pipelined_kernel(*refs, program, scale, nb, tb, tk):
    n = len(program)
    g_refs, x_hbm, o_ref = refs[:n], refs[n:2 * n], refs[-1]

    def body(sems, **bufs):
        xs = [bufs[f"x{m}"] for m in range(n)]

        def dma(m, slot, i):
            return pltpu.make_async_copy(x_hbm[m].at[i], xs[m].at[slot],
                                         sems.at[m, slot])

        for m in range(n):                # warm-up: batch tile 0, slot 0
            dma(m, 0, 0).start()

        def step(i, carry):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < nb)
            def _prefetch():              # next tile streams during compute
                for m in range(n):
                    dma(m, nxt, i + 1).start()

            for m in range(n):
                dma(m, slot, i).wait()
            y = carry_body(program, lambda m, u: g_refs[m][0, u],
                           lambda m, e: xs[m][slot, e], tb, tk)
            o_ref[pl.ds(pl.multiple_of(i * tb, tb), tb), :] = y * scale
            return carry

        jax.lax.fori_loop(0, nb, step, 0)

    pl.run_scoped(body,
                  sems=pltpu.SemaphoreType.DMA((n, 2)),
                  **{f"x{m}": pltpu.VMEM((2,) + x_hbm[m].shape[1:],
                                         jnp.float32) for m in range(n)})


@functools.partial(jax.jit, static_argnames=("program", "tk", "tb",
                                             "scale", "interpret"))
def carry_sweep_project_pipelined(*operands: jnp.ndarray, program, tk: int,
                                  tb: int, scale: float,
                                  interpret: bool) -> jnp.ndarray:
    """Double-buffered carry sweep: same contract as `carry_sweep_project`,
    laid out as grid = (k/TK,) with the batch axis swept by an in-kernel
    fori_loop over input slabs held in `memory_space=ANY`."""
    n = len(program)
    g, x = operands[:n], operands[n:]
    nk, nb = g[0].shape[0], x[0].shape[0]
    in_specs = [pl.BlockSpec((1,) + a.shape[1:], lambda ik: (ik, 0, 0, 0))
                for a in g]
    in_specs += [pl.BlockSpec(memory_space=pltpu.ANY) for _ in x]
    return pl.pallas_call(
        functools.partial(_carry_pipelined_kernel, program=program,
                          scale=scale, nb=nb, tb=tb, tk=tk),
        name="carry_sweep_project_pipelined",
        grid=(nk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nb * tb, tk), lambda ik: (0, ik)),
        out_shape=jax.ShapeDtypeStruct((nb * tb, nk * tk), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*operands)
