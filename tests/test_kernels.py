"""Mode-sweep Pallas kernels vs pure-jnp oracle (ref.py), interpret=True.

Sweeps orders 2-5, shapes (aligned and ragged), k values (padding path),
ranks, batch sizes (ragged B included), both directions, and the planner.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sample_cp_rp, sample_tt_rp
from repro.kernels import (cp_project, cp_reconstruct, pick_tiles,
                           plan_contraction, ref, tt_cores_squeezed,
                           tt_project, tt_reconstruct)

SHAPES = [
    (16, 32, 24),      # ragged-ish
    (8, 128, 64),      # lane-aligned tail
    (32, 16, 16),
]
KS = [64, 128, 200]

# one ragged shape per order 2-5 (every mode-count hits the sweep loop
# differently: no interior cores, one, two, three)
ORDER_SHAPES = [(16, 24), (16, 32, 24), (8, 6, 4, 10), (4, 6, 4, 8, 4)]


@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("rank", [1, 3])
def test_tt_project_kernel(dims, k, rank):
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, k, rank)
    x = jax.random.normal(jax.random.PRNGKey(1), dims)
    got = tt_project(op, x, interpret=True)
    want = ref.tt_project_ref(x, tt_cores_squeezed(op)) / jnp.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(op.project(x)),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("rank", [1, 4])
def test_cp_project_kernel(dims, k, rank):
    op = sample_cp_rp(jax.random.PRNGKey(0), dims, k, rank)
    x = jax.random.normal(jax.random.PRNGKey(1), dims)
    got = cp_project(op, x, interpret=True)
    want = ref.cp_project_ref(x, op.factors) / jnp.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# (the structured-input TT x TT kernel coverage that lived here moved to
# tests/test_struct.py with the carry-sweep subsystem, which replaced the
# order-3-only tt_dot kernel)

# ---------------------------------------------------------------------------
# order-N sweep: batched kernels vs vmap-of-reference (interpret mode)
# ---------------------------------------------------------------------------

BATCHES = [1, 3, 5, 16]   # ragged (3, 5) exercise batch-tile padding


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("dims", ORDER_SHAPES)
@pytest.mark.parametrize("k", [96, 200])
def test_tt_sweep_all_orders_vs_refs(b, dims, k):
    """Order 2-5 project AND reconstruct == references and the operator's
    own einsum paths (non-power-of-two k covers the k-padding path)."""
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, k, 2)
    cores = tt_cores_squeezed(op)
    xb = jax.random.normal(jax.random.PRNGKey(1), (b,) + dims)
    got = tt_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    want = jax.vmap(lambda x: ref.tt_project_ref(x, cores))(xb)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want) / np.sqrt(float(k)),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(op.project(xb)),
                               rtol=2e-4, atol=2e-4)
    y = jax.random.normal(jax.random.PRNGKey(2), (b, k))
    gr = tt_reconstruct(op, y, interpret=True)
    assert gr.shape == (b,) + dims
    wr = ref.tt_reconstruct_ref(y, cores) / np.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(gr),
                               np.asarray(jax.vmap(op.reconstruct)(y)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("dims", ORDER_SHAPES)
@pytest.mark.parametrize("k", [96, 200])
def test_cp_sweep_all_orders_vs_refs(b, dims, k):
    op = sample_cp_rp(jax.random.PRNGKey(0), dims, k, 3)
    xb = jax.random.normal(jax.random.PRNGKey(1), (b,) + dims)
    got = cp_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    want = jax.vmap(lambda x: ref.cp_project_ref(x, op.factors))(xb)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want) / np.sqrt(float(k)),
                               rtol=3e-5, atol=3e-5)
    y = jax.random.normal(jax.random.PRNGKey(2), (b, k))
    gr = cp_reconstruct(op, y, interpret=True)
    assert gr.shape == (b,) + dims
    wr = ref.cp_reconstruct_ref(y, op.factors) / np.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(gr),
                               np.asarray(jax.vmap(op.reconstruct)(y)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dims,k", [((16, 32, 24), 200), ((8, 128, 64), 128)])
def test_tt_project_batched_vs_vmap_ref(b, dims, k):
    """Batched kernel == vmap of the unbatched reference, with the fused
    1/sqrt(k) scaling (ragged B exercises the batch-tile padding)."""
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, k, 2)
    cores = tt_cores_squeezed(op)
    xb = jax.random.normal(jax.random.PRNGKey(1), (b,) + dims)
    got = tt_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    want = jax.vmap(lambda x: ref.tt_project_ref(x, cores))(xb)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want) / np.sqrt(float(k)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dims,k", [((16, 32, 24), 200), ((8, 128, 64), 128)])
def test_cp_project_batched_vs_vmap_ref(b, dims, k):
    """The oracle runs in float64: at (8, 128, 64) its own float32
    rounding (~2.4e-5) exceeds the tolerance, so a float32 oracle only
    matches a kernel that replays its exact summation order."""
    op = sample_cp_rp(jax.random.PRNGKey(0), dims, k, 3)
    xb = jax.random.normal(jax.random.PRNGKey(1), (b,) + dims)
    got = cp_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    with jax.enable_x64(True):
        f64 = [jnp.asarray(np.asarray(f), jnp.float64) for f in op.factors]
        want = jax.vmap(lambda x: ref.cp_project_ref(x, f64))(
            jnp.asarray(np.asarray(xb), jnp.float64))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want) / np.sqrt(float(k)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("k", [128, 200])
def test_tt_reconstruct_batched_vs_vmap_ref(b, dims, k):
    """Adjoint kernel == the reference einsum chain == vmap of
    op.reconstruct, ragged B and non-power-of-two k included."""
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, k, 2)
    y = jax.random.normal(jax.random.PRNGKey(1), (b, k))
    got = tt_reconstruct(op, y, interpret=True)
    assert got.shape == (b,) + dims
    want = ref.tt_reconstruct_ref(y, tt_cores_squeezed(op)) / np.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.vmap(op.reconstruct)(y)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("k", [128, 200])
def test_cp_reconstruct_batched_vs_vmap_ref(b, dims, k):
    op = sample_cp_rp(jax.random.PRNGKey(0), dims, k, 3)
    y = jax.random.normal(jax.random.PRNGKey(1), (b, k))
    got = cp_reconstruct(op, y, interpret=True)
    assert got.shape == (b,) + dims
    want = ref.cp_reconstruct_ref(y, op.factors) / np.sqrt(float(k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.vmap(op.reconstruct)(y)),
                               rtol=1e-5, atol=1e-5)


def test_reconstruct_unbatched_matches_op():
    """(k,) in, in_dims-shaped out — the single-sketch contract survives."""
    dims, k = (16, 32, 24), 128
    for sampler, kern in ((sample_tt_rp, tt_reconstruct),
                          (sample_cp_rp, cp_reconstruct)):
        op = sampler(jax.random.PRNGKey(0), dims, k, 2)
        y = jax.random.normal(jax.random.PRNGKey(1), (k,))
        got = kern(op, y, interpret=True)
        assert got.shape == dims
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(op.reconstruct(y)),
                                   rtol=1e-5, atol=1e-5)


def test_fused_scaling_matches_explicit():
    """The epilogue-fused 1/sqrt(k) equals the raw contraction scaled after —
    scaling each k-tile partial sum commutes with the d1 accumulation."""
    from repro.kernels._sweep import sweep_project
    from repro.kernels.ops import dense_blocks_view, sweep_operands
    dims, k = (16, 32, 24), 128
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, k, 2)
    xb = jax.random.normal(jax.random.PRNGKey(1), (4,) + dims)
    plan = plan_contraction("tt", "project", k, 4, dims, 2)
    args = (dense_blocks_view(plan, xb),
            *sweep_operands("tt", tt_cores_squeezed(op), plan))
    tiles = dict(steps=plan.steps, tk=plan.tk, tb=plan.tb, ba=plan.ba,
                 interpret=True)
    raw = sweep_project(*args, scale=1.0, **tiles)[:4]
    fused = sweep_project(*args, scale=1.0 / float(np.sqrt(k)),
                          **tiles)[:4]
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(raw) / np.sqrt(float(k)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_pick_tiles_respects_vmem_budget():
    """Tiles respect TPU block alignment — TK the 128-lane width (or all of
    a smaller, padded k), TB eight sublanes (or all of a batch of at most
    eight) — and the leading-mode tile BA shrinks through the aligned
    divisors of d1 until the accounted footprint fits the budget."""
    from repro.kernels.ops import VMEM_BUDGET_BYTES, plan_contraction
    for dims in [(128, 128, 64), (32, 32, 32, 32), (16, 16, 16, 16, 16)]:
        for kind in ("project", "reconstruct"):
            tk, tb, ba = pick_tiles(1024, 96, dims, 2, kind=kind)
            assert tk == 128 and tb == 8
            assert dims[0] % ba == 0 and (ba % 8 == 0 or ba in (1, dims[0]))
            plan = plan_contraction("tt", kind, 1024, 96, dims, 2)
            assert plan.vmem_bytes <= VMEM_BUDGET_BYTES
    # the big order-3 bucket sheds the leading tile; tiny problems keep
    # whole-array tiles, at every order
    assert pick_tiles(1024, 96, (128, 128, 64), 2)[2] < 128
    assert pick_tiles(64, 2, (8, 8, 8), 2, kind="project") == (64, 8, 8)
    assert pick_tiles(64, 2, (8, 8, 8, 8), 2, kind="project") == (64, 8, 8)
    # a budget nothing fits is a typed error, not a warning
    from repro.kernels import KernelPlanError
    with pytest.raises(KernelPlanError, match="VMEM"):
        pick_tiles(1024, 96, (128, 128, 64), 2, budget=1 << 20)
    with pytest.raises(ValueError, match="unknown kind"):
        pick_tiles(64, 2, (8, 8, 8), 2, kind="nope")


def test_plan_contraction_emits_order3_program():
    """The planner's program at order 3: one matmul per bond against the
    last core, then a reduce per remaining mode (TT couples every bond,
    an interior CP factor keeps r on r); the adjoint is its reverse."""
    plan = plan_contraction("tt", "project", 256, 4, (8, 128, 64), 2)
    assert plan.steps == (("dot", 2, 64), ("reduce", "full", 2, 2, 128),
                          ("reduce", "full", 1, 2, 8))
    assert plan.grid == (2, 1, 1) and plan.order == 3
    assert plan_contraction("tt", "reconstruct", 256, 4, (8, 128, 64),
                            2).steps == (("expand", "full", 1, 2, 8),
                                         ("expand", "full", 2, 2, 128),
                                         ("dot", 2, 64))
    cp_plan = plan_contraction("cp", "project", 256, 4, (8, 128, 64), 2)
    assert cp_plan.steps[1] == ("reduce", "diag", 2, 1, 128)
    # trailing modes merge while their product stays <= MERGE_CAP
    merged = plan_contraction("tt", "project", 256, 4, (8, 64, 16, 8), 2)
    assert merged.kdims == (8, 64, 128)
    assert merged.steps[0] == ("dot", 2, 128)


def test_plan_contraction_rejects_bad_requests():
    with pytest.raises(ValueError, match="order >= 2"):
        plan_contraction("tt", "project", 64, 1, (64,), 2)
    with pytest.raises(ValueError, match="unknown family"):
        plan_contraction("tucker", "project", 64, 1, (8, 8), 2)
    with pytest.raises(ValueError, match="MAX_ORDER"):
        plan_contraction("tt", "project", 64, 1, (2,) * 9, 2)


def test_kernel_fallback_order1():
    """Order-1 operators (classical Gaussian RP as TT) fall back to the
    core einsum path — there is no mode to sweep."""
    op = sample_tt_rp(jax.random.PRNGKey(0), (64,), 32, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (64,))
    np.testing.assert_allclose(np.asarray(tt_project(op, x, interpret=True)),
                               np.asarray(op.project(x)), rtol=1e-5)
    y = jax.random.normal(jax.random.PRNGKey(2), (32,))
    np.testing.assert_allclose(np.asarray(tt_reconstruct(op, y, interpret=True)),
                               np.asarray(op.reconstruct(y)), rtol=1e-5)


def test_kernel_bf16_inputs():
    dims = (8, 32, 16)
    op = sample_tt_rp(jax.random.PRNGKey(0), dims, 128, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), dims)
    got16 = tt_project(op, x.astype(jnp.bfloat16), interpret=True)
    want = op.project(x)
    np.testing.assert_allclose(np.asarray(got16, dtype=np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)
