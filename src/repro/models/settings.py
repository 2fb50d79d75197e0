"""Trace-time model settings (attention chunking, scan unrolling).

A contextvar consulted while tracing — NOT a runtime value. The dry-run's
cost probes set unroll_scans=True so XLA's cost analysis sees every loop
iteration (lax.scan bodies are otherwise counted once); real training keeps
rolled scans for fast compiles and small HLO.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelSettings:
    # attention memory-efficiency knobs
    attn_chunk_q: int = 512
    attn_chunk_k: int = 1024
    dense_below: int = 2048 * 2048   # use dense scores for Sq*Sk <= this
    ce_chunk: int = 512
    # cost-probe mode: fully unroll scans so HLO cost analysis is exact
    unroll_scans: bool = False
    # pjit mesh for internal sharding constraints (set by launch/steps.py at
    # trace time; None on single-device paths)
    mesh: object = None
    # mesh axes currently under shard_map manual control — excluded from
    # with_sharding_constraint specs (e.g. 'pod' in the compressed train step)
    manual_axes: tuple = ()
    # §Perf knobs (hypothesis -> change -> measure; see EXPERIMENTS.md):
    # cast f32 params to compute dtype ONCE before the layer scan, so FSDP
    # all-gathers move bf16 instead of f32
    cast_params_once: bool = False
    # cast softmax weights to bf16 for the PV matmul (scores stay f32)
    flash_p_bf16: bool = False
    # constrain attention/FFN block outputs to the sequence-sharded layout
    # BEFORE the residual add, so row-parallel partial sums lower to
    # reduce-scatter instead of all-reduce (Megatron-SP)
    sp_block_outputs: bool = False
    # pin q/k/v to (batch->dp, heads->model) inside flash attention; OFF lets
    # the partitioner pick (cheaper collectives on some dense stacks)
    constrain_attn_heads: bool = True
    # expand KV heads to Hq inside flash so the head axis shards at TP>Hkv;
    # OFF (default after §Perf hc8: -20% memory term, -5% collectives on
    # deepseek train_4k) keeps the grouped (Hkv, G) layout with batch-pinned
    # constraints; flash chunking + remat keeps score blocks bounded anyway
    gqa_expand: bool = False
    # when experts don't divide the model axis (mixtral E=8 < 16): shard the
    # expert-buffer CAPACITY dim over 'model' so the down-proj partial sums
    # lower to reduce-scatter instead of a full all-reduce
    moe_c_shard: bool = False


_settings: contextvars.ContextVar[ModelSettings] = contextvars.ContextVar(
    "repro_model_settings", default=ModelSettings())


def get() -> ModelSettings:
    return _settings.get()


def scan_unroll():
    """Value to pass as lax.scan(..., unroll=...)."""
    return True if _settings.get().unroll_scans else 1


@contextlib.contextmanager
def override(**kw):
    if kw.get("mesh") is not None:
        # runtime import: launch/mesh imports only jax
        from repro.launch.mesh import auto_axes
        kw["mesh"] = auto_axes(kw["mesh"])
    cur = _settings.get()
    token = _settings.set(dataclasses.replace(cur, **kw))
    try:
        yield _settings.get()
    finally:
        _settings.reset(token)
