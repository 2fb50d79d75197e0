"""pjit-compiled step builders: train_step / prefill_step / serve_step.

Each builder returns (jitted_fn, arg_shapes, arg_shardings) so the dry-run can
.lower(...).compile() against ShapeDtypeStructs and real launches can call
the same function with live arrays.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import Model, input_specs
from repro.models import settings as model_settings
from repro.models.config import ArchConfig, ShapeSpec
from repro.optim import AdamWConfig, adamw, schedule

from . import sharding as sh
from .mesh import auto_axes, data_axes, model_size


def _policy(cfg: ArchConfig):
    if cfg.policy == "lean":
        return dict(param_dtype=jnp.bfloat16, moment_dtype=jnp.bfloat16,
                    compute_dtype=jnp.bfloat16)
    return dict(param_dtype=jnp.float32, moment_dtype=jnp.float32,
                compute_dtype=jnp.bfloat16)


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def moe_groups_for(cfg: ArchConfig, mesh, global_batch: int) -> int:
    """Dispatch groups == number of data shards that divide the batch."""
    if cfg.moe is None:
        return 1
    dp = 1
    for a in data_axes(mesh):
        dp *= mesh.shape[a]
    g = dp
    while g > 1 and global_batch % g:
        g //= 2
    return max(1, g)


@dataclasses.dataclass
class StepBundle:
    fn: Callable            # jitted
    args: tuple             # ShapeDtypeStructs (for .lower)
    shardings: tuple        # matching shardings
    notes: list


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def build_train_step(model: Model, mesh, shape: ShapeSpec, *,
                     opt: AdamWConfig | None = None,
                     lr_fn: Callable | None = None,
                     remat: str = "nothing",
                     seq_parallel: bool = True,
                     compressor=None,
                     fused_update: bool = False) -> StepBundle:
    """`fused_update=True` swaps the compress -> adamw.update chain of the
    single-pod compressed branch for `adamw.update_sketched` — one fused
    unsketch+EF+AdamW kernel launch per leaf, no dense g_hat in HBM.
    Requires a compressor, no pod axis (the collective branch syncs
    sketches across pods before the optimizer and keeps the unfused
    update), and `AdamWConfig(clip_norm=None)`."""
    mesh = auto_axes(mesh)
    cfg = model.cfg
    pol = _policy(cfg)
    opt = opt or AdamWConfig(moment_dtype=pol["moment_dtype"])
    lr_fn = lr_fn or functools.partial(
        schedule.cosine_with_warmup, peak_lr=3e-4, warmup_steps=2000,
        total_steps=100_000)
    notes: list = []

    # shapes & shardings -------------------------------------------------
    # With the sketch compressor, params replicate across pods (DDP-of-FSDP):
    # the pod axis is synced exclusively through the compressed all-reduce.
    compressing = compressor is not None
    has_pod = "pod" in mesh.axis_names
    fsdp_axes = ("data",) if (compressing and has_pod) else None
    pod_axis = "pod" if (compressing and has_pod) else None
    if fused_update:
        if not compressing:
            raise ValueError(
                "fused_update=True needs a compressor: the fused kernel IS "
                "the unsketch — without sketch compression there is "
                "nothing to fuse; pass compressor= or drop fused_update")
        if pod_axis is not None:
            raise ValueError(
                "fused_update=True is wired for the single-pod roundtrip "
                "branch; the pod-collective branch syncs sketches across "
                "pods before the optimizer and keeps the unfused update — "
                "run without a 'pod' mesh axis or drop fused_update")
        if opt.clip_norm is not None:
            raise ValueError(
                "fused_update=True fuses AdamW into the unsketch kernel, "
                "which never materializes the dense gradient estimate to "
                "clip; construct AdamWConfig(clip_norm=None)")
    if compressing:
        # explicit bucket-axis layout for the sketcher: data axes minus the
        # manual pod axis (replaces the legacy global _constrain_buckets
        # hint), and the mesh the collective shard_map runs on
        compressor = dataclasses.replace(
            compressor, pod_axis=pod_axis, mesh=mesh,
            bucket_spec=sh.bucket_specs(
                mesh, exclude=(pod_axis,) if pod_axis else ()))
    param_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=pol["param_dtype"]))
    axes = model.param_axes()
    pspecs = sh.param_specs(cfg, axes, mesh, param_shapes, notes,
                            fsdp_axes=fsdp_axes)
    opt_shapes = jax.eval_shape(lambda: adamw.init_state(param_shapes, opt))
    ospecs = {"m": pspecs, "v": pspecs, "count": P()}
    state_shapes = {"params": param_shapes, "opt": opt_shapes}
    state_specs = {"params": pspecs, "opt": ospecs}
    npod = mesh.shape["pod"] if has_pod else 1
    if compressing:
        # per-pod residual: leading pod dim on every leaf
        def _ef_shapes():
            base = jax.eval_shape(compressor.init_state, param_shapes)
            if pod_axis is None:
                return base
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((npod,) + s.shape, s.dtype),
                base)
        ef_shapes = _ef_shapes()
        state_shapes["ef"] = ef_shapes
        # per-pod residuals inherit the param FSDP/TP sharding behind the
        # leading pod dim (a bare P('pod') would replicate 4 bytes/param of
        # residual on every device in the pod)
        state_specs["ef"] = ({"residual": jax.tree.map(
            lambda spec: P(pod_axis, *spec), pspecs,
            is_leaf=lambda x: isinstance(x, P))}
            if pod_axis else jax.tree.map(lambda s: P(), ef_shapes))

    batch_shapes = input_specs(cfg, shape)
    batch_specs = sh.input_batch_specs(batch_shapes, mesh)

    groups = moe_groups_for(cfg, mesh, shape.global_batch)
    if pod_axis is not None:
        groups = max(1, groups // npod)
    constrain = (functools.partial(
        sh.shard_batch_seq, mesh=mesh,
        exclude=(pod_axis,) if pod_axis else ()) if seq_parallel else None)

    def loss_and_grads(params, batch):
        def loss_f(p):
            if model_settings.get().cast_params_once:
                # pre-cast matrices so FSDP all-gathers move bf16, not f32
                # (vectors — norms/biases — stay f32 for stability)
                p = jax.tree.map(
                    lambda a: a.astype(pol["compute_dtype"])
                    if (a.dtype == jnp.float32 and a.ndim >= 2) else a, p)
            return model.loss_fn(p, batch, compute_dtype=pol["compute_dtype"],
                                 remat=remat, moe_groups=groups,
                                 constrain=constrain)
        with model_settings.override(
                mesh=mesh,
                manual_axes=(pod_axis,) if pod_axis else ()):
            return jax.value_and_grad(loss_f)(params)

    def train_step(state, batch):
        params = state["params"]
        metrics = {}
        new_state = dict(state)
        if fused_update:
            # single-pod compressed branch, fused: ONE unsketch+EF+AdamW
            # kernel launch per leaf — no dense g_hat in HBM, no separate
            # optimizer pass
            loss, grads = loss_and_grads(params, batch)
            lr = lr_fn(state["opt"]["count"])
            new_p, new_opt, new_state["ef"], cmet = adamw.update_sketched(
                params, grads, state["ef"], state["opt"], lr, opt,
                compressor=compressor)
            metrics.update(cmet)
            metrics["loss"] = loss
            metrics["lr"] = lr
            new_state["params"] = new_p
            new_state["opt"] = new_opt
            return new_state, metrics
        if not compressing:
            loss, grads = loss_and_grads(params, batch)
        elif pod_axis is None:
            # single-pod mesh: roundtrip estimator (no comm term), same math
            loss, grads = loss_and_grads(params, batch)
            grads, new_state["ef"], cmet = compressor.compress(
                grads, state["ef"], step=state["opt"]["count"])
            metrics.update(cmet)
        else:
            # per-pod grads via vmap(spmd_axis_name='pod'): the batch gets a
            # leading npod dim sharded over 'pod'; the ONLY cross-pod comm is
            # the mean over that dim of the (buckets, k) sketches.
            def split_pod(x, bdim):
                if bdim == 0:
                    return x.reshape((npod, x.shape[0] // npod) + x.shape[1:])
                assert bdim == 1  # positions3: (3, B, S)
                y = x.reshape((x.shape[0], npod, x.shape[1] // npod)
                              + x.shape[2:])
                return jnp.moveaxis(y, 1, 0)

            batch_pp = {k: split_pod(v, 1 if k == "positions3" else 0)
                        for k, v in batch.items()}
            per_pod = jax.vmap(
                lambda b: loss_and_grads(params, b),
                in_axes=({k: 0 for k in batch_pp},),
                spmd_axis_name=pod_axis)
            loss_pp, grads_pp = per_pod(batch_pp)
            # re-assert FSDP/TP sharding on the per-pod grads: sharding does
            # not reliably survive the spmd vmap, and replicated 67B-param
            # grad trees are fatal at production scale
            grads_pp = jax.tree.map(
                lambda g, spec: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P(pod_axis, *spec))),
                grads_pp, pspecs,
                is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))
            loss = jnp.mean(loss_pp)
            # REAL collective sync: shard_map manual over 'pod' — the only
            # cross-pod traffic is the pmean inside compress_collective
            # ((buckets, k) floats under sync='sketch-mean')
            grads, new_state["ef"], cmet = compressor.compress_collective(
                grads_pp, state["ef"], step=state["opt"]["count"])
            metrics.update(cmet)
        metrics["loss"] = loss
        lr = lr_fn(state["opt"]["count"])
        new_p, new_opt, omet = adamw.update(params, grads, state["opt"], lr, opt)
        metrics.update(omet)
        metrics["lr"] = lr
        new_state["params"] = new_p
        new_state["opt"] = new_opt
        return new_state, metrics

    fn = jax.jit(
        train_step,
        in_shardings=(_named(mesh, state_specs), _named(mesh, batch_specs)),
        out_shardings=(_named(mesh, state_specs), None),
        donate_argnums=(0,),
    )
    return StepBundle(fn, (state_shapes, batch_shapes),
                      (state_specs, batch_specs), notes)


def init_train_state(model: Model, key, *, opt: AdamWConfig | None = None,
                     compressor=None, npod: int = 1) -> dict:
    pol = _policy(model.cfg)
    opt = opt or AdamWConfig(moment_dtype=pol["moment_dtype"])
    params = model.init(key, dtype=pol["param_dtype"])
    state = {"params": params, "opt": adamw.init_state(params, opt)}
    if compressor is not None:
        ef = compressor.init_state(params)
        if npod > 1:  # per-pod residuals: leading pod dim
            ef = jax.tree.map(
                lambda e: jnp.zeros((npod,) + e.shape, e.dtype), ef)
        state["ef"] = ef
    return state


# ---------------------------------------------------------------------------
# Prefill (inference forward over the full prompt)
# ---------------------------------------------------------------------------

def build_prefill_step(model: Model, mesh, shape: ShapeSpec, *,
                       remat: str = "nothing",
                       seq_parallel: bool = True) -> StepBundle:
    mesh = auto_axes(mesh)
    cfg = model.cfg
    pol = _policy(cfg)
    notes: list = []
    param_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=pol["param_dtype"]))
    pspecs = sh.param_specs(cfg, model.param_axes(), mesh, param_shapes, notes)
    batch_shapes = input_specs(cfg, shape)
    batch_specs = sh.input_batch_specs(batch_shapes, mesh)
    groups = moe_groups_for(cfg, mesh, shape.global_batch)
    constrain = (functools.partial(sh.shard_batch_seq, mesh=mesh)
                 if seq_parallel else None)

    def prefill_step(params, batch):
        mod = model.mod
        # prefill: per-device batch is small, so the grouped (Hkv, G) flash
        # layout cannot shard its score blocks — expand KV heads here
        # (train keeps the grouped layout; see EXPERIMENTS.md §Perf hc8/hc9)
        ctx = model_settings.override(mesh=mesh, gqa_expand=True,
                                      constrain_attn_heads=True)
        ctx.__enter__()
        if cfg.family == "encdec":
            enc = mod.encode(cfg, params, batch["frames"],
                             compute_dtype=pol["compute_dtype"], remat=remat)
            h = mod.decode_hidden(cfg, params, batch["tokens"], enc,
                                  compute_dtype=pol["compute_dtype"],
                                  remat=remat)
        else:
            h = mod.forward_hidden(cfg, params, batch["tokens"],
                                   positions3=batch.get("positions3"),
                                   patches=batch.get("patches"),
                                   patch_positions=batch.get("patch_positions"),
                                   compute_dtype=pol["compute_dtype"],
                                   remat=remat, moe_groups=groups,
                                   constrain=constrain)
        unembed = (params["embed"].T if cfg.tie_embeddings or
                   "unembed" not in params else params["unembed"])
        logits = h[:, -1, :].astype(jnp.float32) @ unembed.astype(jnp.float32)
        ctx.__exit__(None, None, None)
        return logits

    fn = jax.jit(prefill_step,
                 in_shardings=(_named(mesh, pspecs), _named(mesh, batch_specs)),
                 out_shardings=None)
    return StepBundle(fn, (param_shapes, batch_shapes),
                      (pspecs, batch_specs), notes)


# ---------------------------------------------------------------------------
# Decode (one new token against a seq_len cache)
# ---------------------------------------------------------------------------

def build_serve_step(model: Model, mesh, shape: ShapeSpec) -> StepBundle:
    mesh = auto_axes(mesh)
    cfg = model.cfg
    pol = _policy(cfg)
    notes: list = []
    param_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=pol["param_dtype"]))
    pspecs = sh.param_specs(cfg, model.param_axes(), mesh, param_shapes, notes)
    batch_shapes = input_specs(cfg, shape)  # token/pos/cache (+positions3)
    cache_shapes = batch_shapes["cache"]
    cspecs = sh.cache_specs(cfg, cache_shapes, mesh)
    tok_spec = sh.batch_spec((shape.global_batch,), mesh)
    groups = moe_groups_for(cfg, mesh, shape.global_batch)

    def serve_step(params, cache, token, pos, positions3=None):
        kw = {"compute_dtype": pol["compute_dtype"], "moe_groups": groups}
        if positions3 is not None:
            kw["positions3"] = positions3
        with model_settings.override(mesh=mesh):
            logits, new_cache = model.decode_step(params, cache, token, pos,
                                                  **kw)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    in_shardings = [_named(mesh, pspecs), _named(mesh, cspecs),
                    NamedSharding(mesh, tok_spec), NamedSharding(mesh, tok_spec)]
    args = [param_shapes, cache_shapes,
            batch_shapes["token"], batch_shapes["pos"]]
    if "positions3" in batch_shapes:
        in_shardings.append(NamedSharding(mesh, P(None, tok_spec[0], None)))
        args.append(batch_shapes["positions3"])
    fn = jax.jit(serve_step,
                 in_shardings=tuple(in_shardings),
                 out_shardings=(NamedSharding(mesh, tok_spec),
                                _named(mesh, cspecs)),
                 donate_argnums=(1,))
    return StepBundle(fn, tuple(args),
                      (pspecs, cspecs, tok_spec, tok_spec), notes)
