"""Double-buffered DMA pipelining vs the serial schedule.

The pipelined kernels (`sweep_project_pipelined`, dense mode sweep;
`carry_sweep_project_pipelined`, structured carry sweep) prefetch the next
grid step's input/core tiles into a second VMEM slot while the current tile
contracts — SAME tiles, SAME order, SAME math, different overlap. These
tests pin (a) numerical equivalence to the serial schedule across orders
2-5 and both families (including the no-overlap na==1 / nb==1 edges where
the pipeline degenerates to serial), (b) the planner's two-slot accounting
and its typed errors, and (c) the `pipeline=` plumbing through
`rp.project`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rp
from repro.core import BatchedCPTensor, BatchedTTTensor, random_cp, random_tt
from repro.kernels import (PIPELINES, cp_project, plan_carry_sweep,
                           plan_contraction, struct_hbm_bytes, sweep_hbm_bytes,
                           tt_project)
from repro.kernels.struct.plan import CarryPlan

ORDER_SHAPES = [(16, 24), (16, 32, 24), (8, 6, 4, 10), (4, 6, 4, 8, 4)]


# ---------------------------------------------------------------------------
# dense sweep: pipelined == serial
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", ORDER_SHAPES)
@pytest.mark.parametrize("family", ["tt", "cp"])
def test_sweep_pipelined_matches_serial(dims, family):
    k, rank, b = 96, 2, 4
    op = rp.make_projector(
        rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
        jax.random.PRNGKey(0))
    xb = jax.random.normal(jax.random.PRNGKey(1), (b,) + dims)
    kern = tt_project if family == "tt" else cp_project
    got = kern(op, xb, interpret=True, pipeline="double")
    want = kern(op, xb, interpret=True, pipeline="serial")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("family", ["tt", "cp"])
def test_sweep_pipelined_na1_edge(family):
    """d1 <= ba: a single grid step — nothing to prefetch, the pipeline
    must still produce the serial result (its steady state never runs)."""
    dims, k, rank = (8, 16, 16), 128, 2
    op = rp.make_projector(
        rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
        jax.random.PRNGKey(2))
    plan = plan_contraction(family, "project", k, 2, dims, rank,
                            pipeline="double")
    assert -(-dims[0] // plan.ba) == 1
    xb = jax.random.normal(jax.random.PRNGKey(3), (2,) + dims)
    kern = tt_project if family == "tt" else cp_project
    np.testing.assert_allclose(
        np.asarray(kern(op, xb, interpret=True, pipeline="double")),
        np.asarray(kern(op, xb, interpret=True, pipeline="serial")), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# carry sweep: pipelined == serial, all four structured pairings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_family", ["tt", "cp"])
@pytest.mark.parametrize("in_family", ["tt", "cp"])
def test_carry_pipelined_matches_serial(op_family, in_family):
    dims, k, r_op, r_in, b = (8, 6, 10), 96, 2, 3, 16
    op = rp.make_projector(
        rp.ProjectorSpec(family=op_family, k=k, dims=dims, rank=r_op),
        jax.random.PRNGKey(4))
    mk = random_tt if in_family == "tt" else random_cp
    items = [mk(jax.random.PRNGKey(10 + i), dims, r_in) for i in range(b)]
    stack = (BatchedTTTensor.stack if in_family == "tt"
             else BatchedCPTensor.stack)
    xb = stack(items)
    got = rp.project(op, xb, backend="pallas", pipeline="double")
    want = rp.project(op, xb, backend="pallas", pipeline="serial")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# planner: two-slot accounting + typed errors
# ---------------------------------------------------------------------------

def test_plan_double_buffer_accounting():
    """The double-buffered plan must account the second slot: its VMEM
    footprint strictly exceeds the serial plan's for the same problem, and
    stays within the budget it was given."""
    from repro.kernels.ops import VMEM_BUDGET_BYTES
    for family in ("tt", "cp"):
        serial = plan_contraction(family, "project", 128, 8, (256, 16, 16), 2)
        double = plan_contraction(family, "project", 128, 8, (256, 16, 16), 2,
                                  pipeline="double")
        assert double.pipeline == "double" and serial.pipeline == "serial"
        assert double.vmem_bytes > serial.vmem_bytes
        assert double.vmem_bytes <= VMEM_BUDGET_BYTES
        # pipelining overlaps transfers, it does not change traffic
        assert sweep_hbm_bytes(double) == sweep_hbm_bytes(serial)


def test_plan_carry_double_buffer_accounting():
    serial = plan_carry_sweep("tt", "tt", 128, 64, (16, 16, 16), 2, 4)
    double = plan_carry_sweep("tt", "tt", 128, 64, (16, 16, 16), 2, 4,
                              pipeline="double")
    assert isinstance(double, CarryPlan) and double.pipeline == "double"
    assert double.vmem_bytes > serial.vmem_bytes
    assert struct_hbm_bytes(double) == struct_hbm_bytes(serial)
    # pipelined grid drops the batch axis (manually swept inside the body)
    assert len(double.grid) == len(serial.grid) - 1


def test_unknown_pipeline_raises():
    with pytest.raises(ValueError, match="unknown pipeline 'triple'"):
        plan_contraction("tt", "project", 64, 2, (8, 8), 2,
                         pipeline="triple")
    with pytest.raises(ValueError, match="unknown pipeline 'triple'"):
        plan_carry_sweep("tt", "tt", 64, 2, (8, 8), 2, 2, pipeline="triple")
    assert PIPELINES == ("serial", "double")


def test_reconstruct_double_raises():
    with pytest.raises(ValueError, match="kind='project' only"):
        plan_contraction("tt", "reconstruct", 64, 2, (8, 8), 2,
                         pipeline="double")


# ---------------------------------------------------------------------------
# rp.project plumbing
# ---------------------------------------------------------------------------

def test_project_pipeline_kwarg_dense_and_validation():
    dims = (8, 16, 16)
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=64, dims=dims, rank=2),
        jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (4,) + dims)
    got = rp.project(op, x, backend="pallas", pipeline="double")
    want = rp.project(op, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # a typo'd pipeline must never silently run serial — even on routes
    # that ignore the kwarg (einsum backend)
    with pytest.raises(ValueError, match="unknown pipeline 'doble'"):
        rp.project(op, x, backend="xla", pipeline="doble")


def test_project_pipeline_ignored_on_einsum_route():
    """backend='xla' has no manual DMA schedule; pipeline='double' must
    still validate and return the same sketch."""
    dims = (8, 16, 16)
    op = rp.make_projector(
        rp.ProjectorSpec(family="cp", k=64, dims=dims, rank=2),
        jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), dims)
    np.testing.assert_allclose(
        np.asarray(rp.project(op, x, backend="xla", pipeline="double")),
        np.asarray(rp.project(op, x, backend="xla")), rtol=1e-6, atol=1e-6)
