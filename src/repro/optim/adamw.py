"""AdamW in pure JAX (no optax in this environment): decoupled weight decay,
bias correction, f32 moment math regardless of storage dtype, global-norm
clipping. Moments stored in the policy dtype ('mixed' -> f32, 'lean' -> bf16).

`update_sketched` is the FUSED sketch-compressed step: instead of
compressor.compress (reconstruct kernel -> dense g_hat in HBM -> EF
residual pass) followed by `update` (three more dense read/write passes),
each dense leaf runs ONE `repro.kernels.fused_update_buckets` launch that
reconstructs the gradient tile-by-tile from the sketch and applies error
feedback and the AdamW math in the kernel epilogue — the dense
reconstruction never materializes in HBM.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float | None = 1.0
    moment_dtype: Any = jnp.float32


def init_state(params, cfg: AdamWConfig) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, cfg.moment_dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


def update(params, grads, state, lr, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    metrics = {}
    if cfg.clip_norm is not None:
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        metrics["grad_norm"] = gnorm
    count = state["count"] + 1
    c1 = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    c2 = 1.0 - cfg.b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m32 = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g32
        v32 = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * g32 * g32
        step = (m32 / c1) / (jnp.sqrt(v32 / c2) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (step + cfg.weight_decay * p32)
        return (p_new.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype))

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}, metrics


def update_sketched(params, grads, ef_state, opt_state, lr,
                    cfg: AdamWConfig, *, compressor):
    """Fused sketch-compressed AdamW step: one kernel launch per leaf.

    Semantically equal (to fp32 kernel tolerance) to the unfused chain

        g_hat, ef', _ = compressor.compress(grads, ef_state,
                                            step=opt_state['count'])
        p', opt', _   = update(params, g_hat, opt_state, lr, cfg)

    but the dense reconstruction g_hat never touches HBM: after the
    (unchanged) sketch launch, each dense leaf's buckets run ONE
    `repro.kernels.fused_update_buckets` launch whose epilogue applies
    error feedback and the AdamW moment/param math to every tile while
    its reconstruction is still in VMEM. The fused path also keeps the
    gradient estimate in float32 end to end (the unfused chain casts it
    through the gradient storage dtype between compress and update).

    Requires `cfg.clip_norm is None` and a dense-leaf tree — both
    enforced with typed errors. `compressor` is a
    `repro.optim.SketchCompressor` whose family must be TT/CP at a
    kernel-supported order (the fused kernel IS the reconstruct sweep).

    Returns (new_params, new_opt_state, new_ef_state, metrics).
    """
    if cfg.clip_norm is not None:
        raise ValueError(
            "update_sketched fuses the optimizer into the unsketch kernel "
            "and never materializes the dense gradient estimate, so a "
            "global-norm clip over it is unavailable; construct "
            "AdamWConfig(clip_norm=None) for the fused path")
    # function-level imports: optim must not depend on rp/kernels at module
    # scope (core <-> rp import cycle)
    from repro import rp
    from repro.core.sketch import _is_struct_leaf
    from repro.kernels import fused_update_buckets

    if any(_is_struct_leaf(leaf) for leaf in jax.tree_util.tree_leaves(
            grads, is_leaf=_is_struct_leaf)):
        raise ValueError(
            "update_sketched supports dense gradient leaves only: "
            "structured (TT/CP-format) leaves reconstruct through the "
            "carry-sweep route and do not map onto the fused bucket "
            "kernel; use compressor.compress + update for such trees")
    sk = compressor._sketcher(grads)
    key = compressor._key(opt_state["count"])
    op = compressor.cfg.operator(key)
    alpha = compressor.cfg.shrinkage()
    p_fed = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e,
                         grads, ef_state["residual"])
    y = sk.sketch(p_fed, key)                       # (n_buckets, k)
    count = opt_state["count"] + 1
    c1 = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    c2 = 1.0 - cfg.b2 ** count.astype(jnp.float32)
    flat_w, treedef = jax.tree.flatten(params)
    flat_pe = jax.tree.leaves(p_fed)
    flat_m = jax.tree.leaves(opt_state["m"])
    flat_v = jax.tree.leaves(opt_state["v"])
    new_w, new_m, new_v, new_r = [], [], [], []
    off = 0
    fused_hbm = 0
    for pe, w, m, v, nb, size, shape in zip(
            flat_pe, flat_w, flat_m, flat_v, sk._nb, sk._sizes, sk._shapes):
        plan = rp.plan_update(op, nb, fused=True)
        rp.count_kernel_dispatch(family=compressor.cfg.family,
                                 structure="fused-update",
                                 order=len(compressor.cfg.dims),
                                 interpret=plan.interpret)
        fused_hbm += plan.cost.hbm_bytes
        r_b, w_b, m_b, v_b = fused_update_buckets(
            op, y[off:off + nb],
            sk._leaf_to_buckets(pe, nb), sk._leaf_to_buckets(w, nb),
            sk._leaf_to_buckets(m, nb), sk._leaf_to_buckets(v, nb),
            lr, c1, c2, alpha=alpha, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
            weight_decay=cfg.weight_decay, interpret=plan.interpret)
        off += nb
        new_r.append(sk._leaf_from_buckets(r_b, size, shape, jnp.float32))
        new_w.append(sk._leaf_from_buckets(w_b, size, shape, w.dtype))
        new_m.append(sk._leaf_from_buckets(m_b, size, shape, m.dtype))
        new_v.append(sk._leaf_from_buckets(v_b, size, shape, v.dtype))
    unflatten = jax.tree.unflatten
    new_ef = {"residual": unflatten(treedef, new_r)}
    metrics = compressor._metrics(sk, new_ef["residual"])
    # the plan layer's analytic HBM ledger for the fused launches this
    # step issued (sum over leaves) — what the perf/fused bench row gates
    metrics["fused_hbm_bytes"] = jnp.asarray(fused_hbm, jnp.float32)
    return (unflatten(treedef, new_w),
            {"m": unflatten(treedef, new_m), "v": unflatten(treedef, new_v),
             "count": count},
            new_ef, metrics)
