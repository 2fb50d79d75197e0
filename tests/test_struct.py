"""Compressed-domain engine (repro.kernels.struct): carry-sweep Pallas
kernels vs the batched einsum oracles vs the dense path, for all four
(operator, input) structured pairings at orders 2-5, batched containers,
the carry planner, and the rp.project dispatch wiring.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rp
from repro.core import (BatchedCPTensor, BatchedTTTensor, CPTensor, TTTensor,
                        random_cp, random_tt, sample_cp_rp, sample_tt_rp)
from repro.kernels import MAX_ORDER, plan_carry_sweep, struct, struct_project
from repro.kernels.struct import ref as sref
from repro.kernels.struct.ops import _in_operands
from repro.kernels.struct.plan import _carry_program, struct_hbm_bytes

KEY = jax.random.PRNGKey(0)
PAIRINGS = [("tt", "tt"), ("tt", "cp"), ("cp", "tt"), ("cp", "cp")]
# one ragged shape per order 2-5 (each order exercises the carry program's
# interior-mode loop differently: zero, one, two, three interior modes)
ORDER_SHAPES = [(16, 24), (16, 32, 24), (8, 6, 4, 10), (4, 6, 4, 8, 4)]


def _make_op(family, dims, k, rank, fold=1):
    sampler = sample_tt_rp if family == "tt" else sample_cp_rp
    return sampler(jax.random.fold_in(KEY, fold), dims, k, rank)


def _make_input(family, dims, rank, fold=2):
    mk = random_tt if family == "tt" else random_cp
    return mk(jax.random.fold_in(KEY, fold), dims, rank)


def _make_batch(family, dims, rank, b, fold=3):
    items = [_make_input(family, dims, rank, fold=fold + i) for i in range(b)]
    stack = BatchedTTTensor.stack if family == "tt" else BatchedCPTensor.stack
    return stack(items)


# ---------------------------------------------------------------------------
# batched containers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ("tt", "cp"))
def test_batched_container_stack_unstack_full(family):
    dims, b = (4, 6, 5), 3
    xb = _make_batch(family, dims, 2, b)
    assert xb.batch == b and xb.dims == dims and xb.order == 3
    items = xb.unstack()
    assert len(items) == b
    full = xb.full()
    assert full.shape == (b,) + dims
    for i in range(b):
        np.testing.assert_allclose(np.asarray(full[i]),
                                   np.asarray(items[i].full()),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(xb[i].full()),
                                   np.asarray(items[i].full()),
                                   rtol=1e-6, atol=1e-6)


def test_batched_container_rejects_mismatched_structure():
    with pytest.raises(ValueError, match="mismatched structure"):
        BatchedTTTensor.stack([random_tt(KEY, (4, 6, 5), 2),
                               random_tt(KEY, (4, 6, 5), 3)])
    with pytest.raises(ValueError, match="mismatched structure"):
        BatchedCPTensor.stack([random_cp(KEY, (4, 6), 2),
                               random_cp(KEY, (6, 4), 2)])
    with pytest.raises(ValueError, match="mixing weighted"):
        BatchedCPTensor.stack([
            random_cp(KEY, (4, 6), 2),
            CPTensor(random_cp(KEY, (4, 6), 2).factors, jnp.ones((2,)))])


def test_batched_cp_weights_roundtrip():
    ws = [jnp.arange(1.0, 4.0), jnp.arange(2.0, 5.0)]
    items = [CPTensor(random_cp(jax.random.fold_in(KEY, i), (4, 6, 5), 3).factors,
                      ws[i]) for i in range(2)]
    xb = BatchedCPTensor.stack(items)
    assert xb.weights is not None and xb.weights.shape == (2, 3)
    np.testing.assert_allclose(np.asarray(xb.full()[1]),
                               np.asarray(items[1].full()),
                               rtol=1e-6, atol=1e-6)
    back = xb.unstack()
    np.testing.assert_allclose(np.asarray(back[0].weights), np.asarray(ws[0]))


def test_batched_containers_are_pytrees():
    xb = _make_batch("tt", (4, 6), 2, 2)
    mapped = jax.tree_util.tree_map(lambda a: 2.0 * a, xb)
    assert isinstance(mapped, BatchedTTTensor)
    cb = _make_batch("cp", (4, 6), 2, 2)
    assert isinstance(jax.jit(lambda t: t)(cb), BatchedCPTensor)


# ---------------------------------------------------------------------------
# carry planner
# ---------------------------------------------------------------------------

def test_carry_program_order3_ttxtt():
    """The emitted program at order 3 is exactly the documented carry
    schedule: create the (R, R~) carry at mode 1, one full (op, input)
    bond update per interior mode, collapse both bonds at mode N."""
    prog = _carry_program("tt", "tt", 3, 2, 4)
    assert prog == (("mode", "full", 1, 2, "full", 1, 4),
                    ("mode", "full", 2, 2, "full", 4, 4),
                    ("mode", "full", 2, 1, "full", 4, 1))
    # cp x cp is the Hadamard form: both interior couplings diagonal
    prog_cc = _carry_program("cp", "cp", 3, 2, 4)
    assert prog_cc[1] == ("mode", "diag", 2, 2, "diag", 4, 4)
    assert prog_cc[-1] == ("mode", "full", 2, 1, "full", 4, 1)


@pytest.mark.parametrize("op_family,in_family", PAIRINGS)
@pytest.mark.parametrize("order", [2, 5, MAX_ORDER])
def test_carry_program_every_step_is_two_operand(op_family, in_family, order):
    """One step per mode, each pairing one operator slab with one input
    slab; the unit bond fans out at mode 1 and back in at mode N."""
    prog = _carry_program(op_family, in_family, order, 2, 3)
    assert len(prog) == order
    assert prog[0][1:4] == ("full", 1, 2) and prog[0][4:] == ("full", 1, 3)
    assert prog[-1][1:4] == ("full", 2, 1) and prog[-1][4:] == ("full", 3, 1)
    for step in prog[1:-1]:
        assert step[1] == ("full" if op_family == "tt" else "diag")
        assert step[4] == ("full" if in_family == "tt" else "diag")


def test_plan_carry_sweep_tiles_and_grid():
    plan = plan_carry_sweep("tt", "tt", 256, 4, (8, 128, 64), 2, 10)
    assert plan.tk == 128 and plan.grid == (2, 1)
    assert plan.carry_bytes == 4 * 4 * 256 * 2 * 10
    assert plan.vmem_bytes <= 8 * 1024 * 1024
    # huge ranks would unroll too many bond updates: a typed error the
    # execution plan records as the kernel route's rejection
    from repro.kernels import KernelPlanError
    with pytest.raises(KernelPlanError, match="unroll"):
        plan_carry_sweep("tt", "tt", 1024, 16, (128, 128, 128), 64, 64)
    assert struct_hbm_bytes(plan) > 0


def test_plan_carry_sweep_rejects_bad_requests():
    with pytest.raises(ValueError, match="2 <= order"):
        plan_carry_sweep("tt", "tt", 64, 1, (64,), 2, 2)
    with pytest.raises(ValueError, match="2 <= order"):
        plan_carry_sweep("tt", "tt", 64, 1, (2,) * (MAX_ORDER + 1), 2, 2)
    with pytest.raises(ValueError, match="operator family"):
        plan_carry_sweep("tucker", "tt", 64, 1, (8, 8), 2, 2)
    with pytest.raises(ValueError, match="input family"):
        plan_carry_sweep("tt", "tucker", 64, 1, (8, 8), 2, 2)


# ---------------------------------------------------------------------------
# kernels vs refs vs dense (the acceptance sweep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_family,in_family", PAIRINGS)
@pytest.mark.parametrize("dims", ORDER_SHAPES)
@pytest.mark.parametrize("k", [96, 200])
def test_carry_sweep_all_orders_vs_ref_and_dense(op_family, in_family,
                                                 dims, k):
    """Orders 2-5, all four pairings, ragged batch: the Pallas carry sweep
    (interpret mode) == the batched einsum oracle == the dense path on the
    materialized batch (non-power-of-two k covers the k-padding path)."""
    b = 3
    op = _make_op(op_family, dims, k, 2)
    xb = _make_batch(in_family, dims, 3, b)
    got = struct_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    want_ref = struct_project(op, xb, use_kernel=False, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=2e-4, atol=2e-4)
    want_dense = op.project(xb.full())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("op_family,in_family", PAIRINGS)
def test_carry_sweep_unbatched_matches_batch_row(op_family, in_family):
    dims, k = (16, 32, 24), 128
    op = _make_op(op_family, dims, k, 3)
    xb = _make_batch(in_family, dims, 2, 4)
    yb = struct_project(op, xb, interpret=True)
    y1 = struct_project(op, xb[1], interpret=True)
    assert y1.shape == (k,)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(yb[1]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", [1, 3, 5, 16])
def test_carry_sweep_ragged_batches(b):
    """Ragged batch sizes exercise the batch-tile padding (zero input cores
    are inert and sliced away)."""
    dims, k = (8, 16, 16), 128
    op = _make_op("tt", dims, k, 2)
    xb = _make_batch("tt", dims, 2, b)
    got = struct_project(op, xb, interpret=True)
    assert got.shape == (b, k)
    want = struct_project(op, xb, use_kernel=False, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_carry_sweep_cp_weights_fold():
    """CP input weights fold into factor 0 (exact by multilinearity) on
    both the kernel and the einsum routes."""
    dims, k = (4, 6, 5), 64
    op = _make_op("tt", dims, k, 2)
    base = random_cp(KEY, dims, 3)
    w = jnp.arange(1.0, 4.0)
    xw = CPTensor(base.factors, w)
    for use_kernel in (True, False):
        got = struct_project(op, xw, use_kernel=use_kernel, interpret=True)
        want = op.project(xw.full())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_struct_refs_match_operator_methods():
    """The batched oracles agree with the (deprecated but kept) per-format
    operator methods — the pre-subsystem einsum paths."""
    dims, k = (4, 6, 5), 96
    tt_op = _make_op("tt", dims, k, 3)
    cp_op = _make_op("cp", dims, k, 3)
    t = _make_input("tt", dims, 2)
    c = _make_input("cp", dims, 2)
    from repro.kernels import tt_cores_squeezed
    scale = 1.0 / np.sqrt(float(k))
    tb = BatchedTTTensor(tuple(x[None] for x in t.cores))
    cb = BatchedCPTensor(tuple(f[None] for f in c.factors))
    cases = [
        (sref.tt_tt_ref(tt_cores_squeezed(tt_op), _in_operands("tt", tb)),
         tt_op.project_tt(t)),
        (sref.tt_cp_ref(tt_cores_squeezed(tt_op), _in_operands("cp", cb)),
         tt_op.project_cp(c)),
        (sref.cp_tt_ref(cp_op.factors, _in_operands("tt", tb)),
         cp_op.project_tt(t)),
        (sref.cp_cp_ref(cp_op.factors, _in_operands("cp", cb)),
         cp_op.project_cp(c)),
    ]
    for raw, want in cases:
        np.testing.assert_allclose(np.asarray(raw[0] * scale),
                                   np.asarray(want), rtol=2e-4, atol=2e-4)


def test_struct_project_order1_falls_back_dense():
    op = _make_op("tt", (64,), 32, 1)
    x = TTTensor((jax.random.normal(KEY, (1, 64, 1)),))
    got = struct_project(op, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(op.project(x.full())),
                               rtol=1e-5, atol=1e-5)


def test_struct_project_typed_errors():
    op = _make_op("tt", (4, 6, 5), 64, 2)
    with pytest.raises(ValueError, match="input dims"):
        struct_project(op, _make_input("tt", (5, 6, 4), 2), interpret=True)
    with pytest.raises(TypeError, match="structured input"):
        struct_project(op, jnp.zeros((4, 6, 5)), interpret=True)
    from repro.core import GaussianRP
    g = GaussianRP(key=KEY, k=8, dim=120)
    with pytest.raises(TypeError, match="TT/CP operator"):
        struct_project(g, _make_input("tt", (4, 6, 5), 2), interpret=True)


# ---------------------------------------------------------------------------
# dispatch wiring (rp.project routes batched structured inputs in ONE launch)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_family,in_family", PAIRINGS)
@pytest.mark.parametrize("dims", [(16, 16), (8, 8, 8, 8), (8, 8, 8, 8, 8)])
def test_dispatch_struct_one_kernel_call_all_orders(op_family, in_family,
                                                    dims):
    """Acceptance: all four pairings at orders 2/4/5 route through the
    carry-sweep kernel under force_pallas, ONE dispatch per batched call
    (no vmap), matching the XLA einsum route."""
    op = rp.make_projector(
        rp.ProjectorSpec(family=op_family, k=128, dims=dims, rank=2), KEY)
    xb = _make_batch(in_family, dims, 2, 3)
    with rp.dispatch_stats() as stats:
        with rp.force_pallas():
            y_kern = rp.project(op, xb, backend="auto")
        assert stats.kernel_calls == 1
        y_xla = rp.project(op, xb, backend="xla")
        assert stats.kernel_calls == 1      # einsum path never dispatches
    assert y_kern.shape == (3, 128)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_xla),
                               rtol=2e-4, atol=2e-4)


def test_dispatch_single_struct_input_kernel_route():
    """Single (unbatched) structured inputs also take the kernel under
    backend='pallas' — including the order-3 TT x TT case the deleted
    tt_dot kernel used to own (no regression)."""
    dims = (16, 32, 24)
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=128, dims=dims, rank=2), KEY)
    x = _make_input("tt", dims, 4)
    with rp.dispatch_stats() as stats:
        y = rp.project(op, x, backend="pallas")
        assert stats.kernel_calls == 1
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(op.project_tt(x)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(op.project(x.full())),
                               rtol=2e-4, atol=2e-4)


def test_dispatch_struct_to_flat_families_densifies():
    dims = (4, 6, 5)
    xb = _make_batch("cp", dims, 2, 3)
    for family in ("gaussian", "sparse"):
        op = rp.make_projector(
            rp.ProjectorSpec(family=family, k=32, dims=dims), KEY)
        y = rp.project(op, xb)
        assert y.shape == (3, 32)
        np.testing.assert_allclose(
            np.asarray(y[1]), np.asarray(rp.project(op, xb[1])),
            rtol=1e-5, atol=1e-5)


def test_dispatch_struct_dim_mismatch_is_typed():
    op = rp.make_projector(
        rp.ProjectorSpec(family="cp", k=32, dims=(4, 6, 5), rank=2), KEY)
    with pytest.raises(rp.FormatMismatchError):
        rp.project(op, _make_batch("tt", (5, 6, 4), 2, 2))


def test_dispatch_out_of_range_struct_order_stays_on_einsum():
    dims = (2,) * (MAX_ORDER + 1)
    op = _make_op("tt", dims, 32, 2)
    x = _make_input("tt", dims, 2)
    with rp.dispatch_stats() as stats:
        y = rp.project(op, x, backend="pallas")
        assert stats.kernel_calls == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(op.project_tt(x)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# sketcher integration (structured leaves, compressed-domain sketching)
# ---------------------------------------------------------------------------

def test_sketcher_structured_leaves_match_dense_path():
    """A tree with TT/CP/batched leaves sketches leaf-for-leaf equal to the
    same tree densified — and unsketch returns dense unbiased estimates of
    the right shapes."""
    from repro.core import PytreeSketcher, SketchConfig
    dims = (4, 4, 8)
    cfg = SketchConfig(family="tt", k=64, rank=2, bucket_elems=128,
                       dims=dims, backend="xla")
    tree = {"w": jax.random.normal(KEY, (16, 8)),
            "t": _make_input("tt", dims, 3),
            "tb": _make_batch("cp", dims, 2, 3)}
    sk = PytreeSketcher(cfg, tree)
    assert sk.n_buckets == 1 + 1 + 3
    y = sk.sketch(tree, jax.random.PRNGKey(1))
    assert y.shape == (5, 64)
    dense_tree = {"w": tree["w"], "t": tree["t"].full(),
                  "tb": tree["tb"].full().reshape(3, -1)}
    y_dense = PytreeSketcher(cfg, dense_tree).sketch(dense_tree,
                                                     jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    recon = sk.unsketch(y, jax.random.PRNGKey(1))
    assert recon["t"].shape == dims
    assert recon["tb"].shape == (3,) + dims
    assert recon["w"].shape == (16, 8)


def test_sketcher_structured_leaf_rejects_wrong_dims():
    from repro.core import PytreeSketcher, SketchConfig
    cfg = SketchConfig(family="tt", k=64, rank=2, bucket_elems=128,
                       dims=(4, 4, 8))
    with pytest.raises(ValueError, match="structured leaf dims"):
        PytreeSketcher(cfg, {"t": _make_input("tt", (8, 4, 4), 2)})


def test_struct_module_exports():
    assert set(struct.__all__) >= {"struct_project", "plan_carry_sweep",
                                   "CarryPlan"}
