"""Fused unsketch + error-feedback + AdamW Pallas kernel.

The unfused sketch-compressed train step runs, per dense leaf,

    g_hat = alpha * Unsketch(y)     (reconstruct kernel -> dense HBM write)
    resid = p - g_hat               (EF residual: two dense reads, one write)
    m/v/w updates                   (AdamW: three dense read/write passes)

which materializes the dense reconstruction g_hat in HBM and then streams
every dense operand again for error feedback and the optimizer math. This
module fuses the whole chain into ONE launch per leaf on the reconstruct
sweep's own grid `(B/TB, d1/BA, k/TK)` (k-tile INNERMOST): each
`(TB, BA*Q, L)` tile accumulates its reconstruction across the k grid
axis in the revisited RESIDUAL output block — the same revisited-block
accumulation as `_sweep._reconstruct_kernel`, with the residual output
doubling as the g_hat accumulator — and the LAST k step runs the epilogue
while the tile is still in VMEM:

    resid = p - g_hat                         (error feedback)
    m32   = b1 m + (1-b1) g_hat               (AdamW moments, f32)
    v32   = b2 v + (1-b2) g_hat^2
    w'    = w - lr ((m32/c1)/(sqrt(v32/c2)+eps) + wd w)

so the dense g_hat NEVER round-trips through HBM. The JLT 1/sqrt(k) and
the MMSE shrinkage alpha fuse into one static per-k-step scale.

Inputs arrive in BUCKET space, all float32 (`PytreeSketcher.
_leaf_to_buckets` casts on the way in, `_leaf_from_buckets` casts back to
the storage dtype on the way out — the same cast points as the unfused
reference, so 'lean'-policy bf16 moments see identical rounding).

`plan_fused_update` budgets the launch: a reconstruct-sweep plan whose
VMEM budget additionally charges the eight dense `(TB, BA*Q, L)` blocks
the fusion keeps resident (p/w/m/v in, resid/w'/m'/v' out).
`fused_hbm_bytes` / `unfused_hbm_bytes` give the analytic HBM traffic of
the two formulations for the SAME plan — the accounting behind the
`perf/fused/*` bench rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cp_rp import CPRP
from repro.core.formats import _prod
from repro.core.tt_rp import TTRP

from ._sweep import (_imap, compiler_params, core_specs, reconstruct_body,
                     reconstruct_loads)
from .ops import (MAX_ORDER, VMEM_BUDGET_BYTES, ContractionPlan, _pad_axis,
                  dense_blocks_view, kernel_order_supported, plan_contraction,
                  sweep_hbm_bytes, sweep_operands, tt_cores_squeezed)


def plan_fused_update(family: str, k: int, b: int, dims: tuple[int, ...],
                      rank: int, *,
                      budget: int = VMEM_BUDGET_BYTES) -> ContractionPlan:
    """Reconstruct-sweep plan for the fused launch: the planner also
    charges the EIGHT dense `(TB, BA*Q, L)` blocks the fusion keeps
    resident (four optimizer inputs, four outputs)."""
    return plan_contraction(family, "reconstruct", k, b, dims, rank,
                            budget=budget, dense_blocks=8)


def fused_hbm_bytes(plan: ContractionPlan) -> int:
    """Analytic HBM traffic of ONE fused launch under `plan`.

    The sweep-side traffic (sketches re-fetched per d1-tile, cores per
    the reconstruct index maps) is `sweep_hbm_bytes` MINUS its dense
    output write — g_hat lives only in the revisited VMEM block — plus
    eight dense passes: p/w/m/v read once each, resid/w'/m'/v' written
    once each.
    """
    dense = 4 * plan.b * _prod(plan.dims)
    return (sweep_hbm_bytes(plan) - dense) + 8 * dense


def unfused_hbm_bytes(plan: ContractionPlan) -> int:
    """Analytic HBM traffic of the UNFUSED chain for the same `plan`.

    The reconstruct launch (`sweep_hbm_bytes`, which includes the dense
    g_hat WRITE) plus the nine dense passes XLA then streams: g_hat and p
    read for the residual, resid written, and w/m/v each read and written
    by the optimizer step.
    """
    dense = 4 * plan.b * _prod(plan.dims)
    return sweep_hbm_bytes(plan) + 9 * dense


def _fused_kernel(y_ref, s_ref, *refs, steps, n_core, scale, b1, b2, eps,
                  wd, nk):
    core_refs = refs[:n_core]
    p_ref, w_ref, m_ref, v_ref = refs[n_core:n_core + 4]
    r_ref, wo_ref, mo_ref, vo_ref = refs[n_core + 4:]
    ik = pl.program_id(2)
    # one reconstruct k-step: the program of _sweep._reconstruct_kernel
    out = reconstruct_body(y_ref[...], reconstruct_loads(core_refs), steps)
    out = out.reshape(r_ref.shape) * scale

    @pl.when(ik == 0)
    def _init():
        r_ref[...] = out

    @pl.when(ik != 0)
    def _acc():
        r_ref[...] += out

    @pl.when(ik == nk - 1)
    def _epilogue():
        # the accumulated block IS alpha * g_hat for this tile; consume it
        # for EF + AdamW while it is still in VMEM, then overwrite it with
        # the residual
        g = r_ref[...]
        lr, c1, c2 = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2]
        w = w_ref[...]
        m32 = b1 * m_ref[...] + (1.0 - b1) * g
        v32 = b2 * v_ref[...] + (1.0 - b2) * g * g
        step = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps)
        r_ref[...] = p_ref[...] - g
        wo_ref[...] = w - lr * (step + wd * w)
        mo_ref[...] = m32
        vo_ref[...] = v32


@functools.partial(jax.jit, static_argnames=("steps", "tk", "tb", "ba",
                                             "scale", "b1", "b2", "eps",
                                             "wd", "interpret"))
def _fused_launch(y, s, *arrs, steps, tk, tb, ba, scale, b1, b2, eps, wd,
                  interpret):
    cores, dense = arrs[:-4], arrs[-4:]
    b, k = y.shape
    _, rows, ell = dense[0].shape
    d1 = cores[0].shape[0]
    assert k % tk == 0 and b % tb == 0 and d1 % ba == 0, (k, tk, b, tb, d1, ba)
    dense_spec = pl.BlockSpec((tb, ba * (rows // d1), ell),
                              _imap(0, 1, None))
    in_specs = [pl.BlockSpec((tb, tk), _imap(0, 2)),
                pl.BlockSpec((1, 4), _imap(None, None))]
    in_specs += core_specs(cores, "reconstruct", tk=tk, ba=ba, k_pos=2,
                           lead_pos=1)
    in_specs += [dense_spec] * 4
    blk = jax.ShapeDtypeStruct(dense[0].shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_kernel, steps=steps, n_core=len(cores),
                          scale=scale, b1=b1, b2=b2, eps=eps, wd=wd,
                          nk=k // tk),
        name="fused_update",
        grid=(b // tb, d1 // ba, k // tk),
        in_specs=in_specs,
        out_specs=(dense_spec,) * 4,
        out_shape=(blk,) * 4,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(y, s, *arrs)


def fused_update_buckets(op, y, p, w, m, v, lr, c1, c2, *, alpha: float,
                         b1: float, b2: float, eps: float,
                         weight_decay: float, interpret: bool):
    """ONE launch: unsketch + error feedback + AdamW for one leaf's buckets.

    op     : a TT/CP operator at a kernel-supported order (the one the
             sketch was drawn with — regenerated from the same key).
    y      : (nb, k) sketch rows of this leaf.
    p      : (nb, *dims) error-fed gradient buckets (g + e), float32.
    w/m/v  : (nb, *dims) param / first-moment / second-moment buckets, f32.
    lr/c1/c2: traced scalars — learning rate and the AdamW bias corrections
             1-b1^t / 1-b2^t (they change every step; statics would retrace).
    alpha  : MMSE shrinkage (`SketchConfig.shrinkage()`), fused with the
             JLT 1/sqrt(k) into the kernel's static scale.

    Returns (resid, w_new, m_new, v_new), each (nb, *dims) float32:
    resid = p - alpha*Unsketch(y) is the next error-feedback state.
    """
    if not isinstance(op, (TTRP, CPRP)):
        raise TypeError(f"fused_update_buckets needs a TT/CP operator, got "
                        f"{type(op).__name__}")
    if not kernel_order_supported(op.order):
        raise ValueError(
            f"fused_update_buckets needs a kernel-supported operator order "
            f"(2..{MAX_ORDER}), got order {op.order}")
    family = "tt" if isinstance(op, TTRP) else "cp"
    cores = tt_cores_squeezed(op) if family == "tt" else op.factors
    nb = y.shape[0]
    dims = tuple(op.in_dims)
    plan = plan_fused_update(family, op.k, nb, dims, op.rank)
    yk = _pad_axis(_pad_axis(y.astype(jnp.float32), 0, plan.tb), 1, plan.tk)
    dense = [dense_blocks_view(plan, a) for a in (p, w, m, v)]
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32),
                      jnp.zeros((), jnp.float32)]).reshape(1, 4)
    out = _fused_launch(yk, scal, *sweep_operands(family, cores, plan),
                        *dense, steps=plan.steps, tk=plan.tk, tb=plan.tb,
                        ba=plan.ba, scale=float(alpha) / math.sqrt(op.k),
                        b1=float(b1), b2=float(b2), eps=float(eps),
                        wd=float(weight_decay), interpret=interpret)
    return tuple(o[:nb].reshape((nb,) + dims) for o in out)


__all__ = ["fused_hbm_bytes", "fused_update_buckets", "plan_fused_update",
           "unfused_hbm_bytes"]
