"""Compile every kernel of the main path for a TPU v5e chip, without one.

The TPU compiler is installed alongside JAX: a chip that is described (a
`v5e:2x2` topology) rather than attached still compiles a program, and
refuses what the chip would refuse — unaligned blocks, contractions Mosaic
cannot lower, more VMEM than a kernel may use. Interpret-mode tests cannot
see any of that. Shapes are the deployment sizes `chip_smoke.py` runs:
the default 2^20-element gradient bucket at k=1024, B=96 buckets, rank 2;
structured inputs at rank 8 with B=1024.

The topology is described inside a module-scoped fixture (never at import
time), and the persistent compilation cache is off around these compiles:
an executable compiled for a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import rp
from repro.core import BatchedCPTensor, BatchedTTTensor, random_cp, random_tt
from repro.kernels import ops as kops
from repro.kernels.fused_update import fused_update_buckets
from repro.kernels.struct import struct_project

K, B, RANK = 1024, 96, 2
ORDERS = [(128, 128, 64), (32, 32, 32, 32), (16, 16, 16, 16, 16)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _operator(family, dims, rank=RANK):
    spec = rp.ProjectorSpec(family=family, k=K, dims=dims, rank=rank)
    return jax.eval_shape(lambda key: rp.make_projector(spec, key),
                          jax.random.PRNGKey(0))


def _compile(sharding, fn, *args):
    compiled = jax.jit(fn).lower(*_on(sharding, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("family", ["tt", "cp"])
@pytest.mark.parametrize("dims", ORDERS, ids=lambda d: "x".join(map(str, d)))
def test_mode_sweep_project_compiles(one_chip, family, dims):
    kern = kops.tt_project if family == "tt" else kops.cp_project
    _compile(one_chip, lambda op, x: kern(op, x, interpret=False),
             _operator(family, dims),
             jax.ShapeDtypeStruct((B,) + dims, jnp.float32))


@pytest.mark.parametrize("family", ["tt", "cp"])
@pytest.mark.parametrize("dims", ORDERS, ids=lambda d: "x".join(map(str, d)))
def test_mode_sweep_reconstruct_compiles(one_chip, family, dims):
    kern = kops.tt_reconstruct if family == "tt" else kops.cp_reconstruct
    _compile(one_chip, lambda op, y: kern(op, y, interpret=False),
             _operator(family, dims),
             jax.ShapeDtypeStruct((B, K), jnp.float32))


@pytest.mark.parametrize("family", ["tt", "cp"])
def test_double_buffered_project_compiles(one_chip, family):
    dims = (32, 32, 32, 32)
    kern = kops.tt_project if family == "tt" else kops.cp_project
    _compile(one_chip,
             lambda op, x: kern(op, x, interpret=False, pipeline="double"),
             _operator(family, dims),
             jax.ShapeDtypeStruct((B,) + dims, jnp.float32))


@pytest.mark.parametrize("pipeline", ["serial", "double"])
@pytest.mark.parametrize("op_family,in_family",
                         [("tt", "tt"), ("tt", "cp"), ("cp", "tt"),
                          ("cp", "cp")])
def test_carry_sweep_compiles(one_chip, op_family, in_family, pipeline):
    dims, n = (128, 128, 64), 1024
    mk = random_tt if in_family == "tt" else random_cp
    stack = (BatchedTTTensor.stack if in_family == "tt"
             else BatchedCPTensor.stack)
    x = jax.eval_shape(
        lambda key: stack([mk(key, dims, 8)] * n), jax.random.PRNGKey(1))
    _compile(one_chip,
             lambda op, xb: struct_project(op, xb, interpret=False,
                                           pipeline=pipeline),
             _operator(op_family, dims), x)


@pytest.mark.parametrize("family", ["tt", "cp"])
@pytest.mark.parametrize("dims", ORDERS[:2],
                         ids=lambda d: "x".join(map(str, d)))
def test_fused_update_compiles(one_chip, family, dims):
    dense = jax.ShapeDtypeStruct((B,) + dims, jnp.float32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def step(op, y, p, w, m, v, lr, c1, c2):
        return fused_update_buckets(op, y, p, w, m, v, lr, c1, c2,
                                    alpha=0.5, b1=0.9, b2=0.95, eps=1e-8,
                                    weight_decay=0.1, interpret=False)

    _compile(one_chip, step, _operator(family, dims),
             jax.ShapeDtypeStruct((B, K), jnp.float32),
             dense, dense, dense, dense, scalar, scalar, scalar)


def test_fused_update_order5_does_not_fit():
    """At order 5 the fused launch's eight resident dense blocks exceed the
    VMEM budget at the aligned tile floor: a typed error, never a kernel
    that fails to allocate on the chip."""
    from repro.kernels import KernelPlanError
    from repro.kernels.fused_update import plan_fused_update
    with pytest.raises(KernelPlanError, match="VMEM"):
        plan_fused_update("tt", K, B, ORDERS[2], RANK)
