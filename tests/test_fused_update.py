"""Fused unsketch + error feedback + AdamW kernel vs the unfused chain.

`kernels.fused_update_buckets` runs ONE Pallas launch per leaf whose
epilogue applies EF and the AdamW moment/param math to every reconstructed
tile while it is still in VMEM; `optim.adamw.update_sketched` is its
optimizer-level entry. These tests pin (a) numerical equivalence to the
reconstruct -> EF -> AdamW reference across orders 2-5 and both families,
(b) the fixed-point planner's budget accounting and the analytic HBM
ledger (fused < unfused), (c) every typed misuse error, and (d) the
update_sketched == compress + update chain identity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rp
from repro.kernels import (fused_hbm_bytes, fused_update_buckets,
                           plan_fused_update, unfused_hbm_bytes)
from repro.kernels.ops import VMEM_BUDGET_BYTES

ORDER_SHAPES = [(16, 24), (16, 32, 24), (8, 6, 4, 10), (4, 6, 4, 8, 4)]
HP = dict(alpha=0.9, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)


def _reference(op, y, p, w, m, v, lr, c1, c2):
    g = HP["alpha"] * rp.reconstruct(op, y, backend="pallas")
    resid = p - g
    m32 = HP["b1"] * m + (1 - HP["b1"]) * g
    v32 = HP["b2"] * v + (1 - HP["b2"]) * g * g
    step = (m32 / c1) / (jnp.sqrt(v32 / c2) + HP["eps"])
    return resid, w - lr * (step + HP["weight_decay"] * w), m32, v32


@pytest.mark.parametrize("dims", ORDER_SHAPES)
@pytest.mark.parametrize("family", ["tt", "cp"])
def test_fused_matches_reference(dims, family):
    k, rank, nb = 96, 2, 3
    op = rp.make_projector(
        rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
        jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    y = jax.random.normal(jax.random.fold_in(key, 0), (nb, k))
    p, w, m, v = (jax.random.normal(jax.random.fold_in(key, i + 1),
                                    (nb,) + dims) for i in range(4))
    v = jnp.abs(v)  # second moment is nonnegative in real trajectories
    lr, c1, c2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.05)
    got = fused_update_buckets(op, y, p, w, m, v, lr, c1, c2, interpret=True,
                               **HP)
    want = _reference(op, y, p, w, m, v, lr, c1, c2)
    for g, r in zip(got, want):
        assert g.shape == (nb,) + dims and g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-5, atol=5e-5)


def test_plan_fused_update_budget():
    """The fixed point must charge the eight resident dense blocks to the
    sweep's budget: the fused plan fits, and never claims bigger tiles
    than the plain reconstruct plan it derives from."""
    from repro.kernels import plan_contraction
    for family in ("tt", "cp"):
        plan = plan_fused_update(family, 128, 8, (64, 16, 16), 2)
        assert plan.kind == "reconstruct" and plan.pipeline == "serial"
        base = plan_contraction(family, "reconstruct", 128, 8, (64, 16, 16), 2)
        assert plan.tb <= base.tb and plan.ba <= base.ba
        extra = 8 * 4 * plan.tb * plan.ba * 16 * 16
        assert plan.vmem_bytes + extra <= VMEM_BUDGET_BYTES


def test_fused_hbm_ledger():
    """Fused traffic strictly beats unfused (the dense write is replaced
    by 8 optimizer passes vs the chain's write + 9 passes) and both are
    monotone in problem size."""
    for family in ("tt", "cp"):
        plan = plan_fused_update(family, 128, 8, (64, 16, 16), 2)
        assert fused_hbm_bytes(plan) < unfused_hbm_bytes(plan)
        dense = 4 * plan.b * 64 * 16 * 16
        # exactly one dense-array round trip saved plus the write itself
        assert unfused_hbm_bytes(plan) - fused_hbm_bytes(plan) == 2 * dense


def test_fused_typed_errors():
    dims, k = (8, 16, 16), 64
    gop = rp.make_projector(
        rp.ProjectorSpec(family="gaussian", k=k, dims=dims), jax.random.PRNGKey(2))
    args = [jnp.zeros((2, k))] + [jnp.zeros((2,) + dims)] * 4
    scal = [jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.05)]
    with pytest.raises(TypeError, match="TT/CP operator"):
        fused_update_buckets(gop, *args, *scal, interpret=True, **HP)
    from repro.kernels import MAX_ORDER
    big = (2,) * (MAX_ORDER + 1)
    top = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=k, dims=big, rank=2),
        jax.random.PRNGKey(3))
    args7 = [jnp.zeros((2, k))] + [jnp.zeros((2,) + big)] * 4
    with pytest.raises(ValueError, match="order"):
        fused_update_buckets(top, *args7, *scal, interpret=True, **HP)


# ---------------------------------------------------------------------------
# optimizer-level entry: update_sketched
# ---------------------------------------------------------------------------

def _setup_tree():
    from repro.core.sketch import SketchConfig
    from repro.optim import adamw
    from repro.optim.compress import SketchCompressor

    cfg = SketchConfig(family="tt", k=128, rank=2, dims=(16, 16, 8),
                       bucket_elems=2048)
    comp = SketchCompressor(cfg)
    acfg = adamw.AdamWConfig(clip_norm=None)
    key = jax.random.PRNGKey(5)
    params = {"w": jax.random.normal(jax.random.fold_in(key, 0), (3000,)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (100, 7))}
    grads = {"w": jax.random.normal(jax.random.fold_in(key, 2), (3000,)),
             "b": jax.random.normal(jax.random.fold_in(key, 3), (100, 7))}
    ef = jax.tree.map(lambda e: e + 0.01, comp.init_state(params))
    opt = adamw.init_state(params, acfg)
    opt = {**opt, "count": jnp.asarray(4, jnp.int32),
           "m": jax.tree.map(lambda p: p * 0.05, params),
           "v": jax.tree.map(lambda p: jnp.abs(p) * 0.01, params)}
    return comp, acfg, params, grads, ef, opt


def test_update_sketched_matches_compress_then_update():
    """The fused optimizer step IS the compress -> update chain (f32
    params/grads, nonzero EF residual, mid-trajectory count) — same
    params, moments, residual, count, and metrics keys."""
    from repro.optim import adamw

    comp, acfg, params, grads, ef, opt = _setup_tree()
    lr = jnp.float32(1e-3)
    g_ref, ef_ref, _ = comp.compress(grads, ef, step=opt["count"])
    p_ref, opt_ref, _ = adamw.update(params, g_ref, opt, lr, acfg)
    p_f, opt_f, ef_f, met = adamw.update_sketched(
        params, grads, ef, opt, lr, acfg, compressor=comp)
    for ref_t, got_t in [(p_ref, p_f), (opt_ref["m"], opt_f["m"]),
                         (opt_ref["v"], opt_f["v"]),
                         (ef_ref["residual"], ef_f["residual"])]:
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5),
            ref_t, got_t)
    assert int(opt_f["count"]) == int(opt_ref["count"]) == 5
    assert {"sketch_bytes", "dense_bytes", "residual_norm"} <= set(met)


def test_update_sketched_chained_steps():
    """Two fused steps back to back stay glued to the unfused chain —
    the EF residual produced by step 1 feeds step 2 identically."""
    from repro.optim import adamw

    comp, acfg, params, grads, ef, opt = _setup_tree()
    lr = jnp.float32(1e-3)
    p_u, opt_u, ef_u = params, opt, ef
    p_f, opt_f, ef_f = params, opt, ef
    for step in range(2):
        g_hat, ef_u, _ = comp.compress(grads, ef_u, step=opt_u["count"])
        p_u, opt_u, _ = adamw.update(p_u, g_hat, opt_u, lr, acfg)
        p_f, opt_f, ef_f, _ = adamw.update_sketched(
            p_f, grads, ef_f, opt_f, lr, acfg, compressor=comp)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4), p_u, p_f)


def test_update_sketched_typed_errors():
    from repro.core import random_tt
    from repro.optim import adamw

    comp, acfg, params, grads, ef, opt = _setup_tree()
    lr = jnp.float32(1e-3)
    with pytest.raises(ValueError, match="clip_norm=None"):
        adamw.update_sketched(params, grads, ef, opt, lr,
                              adamw.AdamWConfig(), compressor=comp)
    struct_g = {"w": random_tt(jax.random.PRNGKey(6), (16, 16, 8), 2)}
    struct_p = {"w": jnp.zeros((2048,))}
    struct_ef = {"residual": {"w": jnp.zeros((2048,))}}
    struct_opt = adamw.init_state(struct_p, acfg)
    with pytest.raises(ValueError, match="dense gradient leaves only"):
        adamw.update_sketched(struct_p, struct_g, struct_ef, struct_opt,
                              lr, acfg, compressor=comp)


def test_build_train_step_fused_validations():
    """The three build-time misuse errors fire before any compile."""
    from repro.configs import get_config, reduced
    from repro.core.sketch import SketchConfig
    from repro.launch import steps
    from repro.models import build_model
    from repro.models.config import ShapeSpec
    from repro.optim.adamw import AdamWConfig
    from repro.optim.compress import SketchCompressor

    cfg = reduced(get_config("llama3.2-3b"))
    model = build_model(cfg)
    shape = ShapeSpec("t", 32, 4, "train")
    comp = SketchCompressor(SketchConfig(
        family="tt", k=1024, rank=8, bucket_elems=4 * 8 * 16,
        dims=(4, 8, 16)))
    opt = AdamWConfig(clip_norm=None)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with mesh:
        with pytest.raises(ValueError, match="needs a compressor"):
            steps.build_train_step(model, mesh, shape, opt=opt,
                                   fused_update=True)
        with pytest.raises(ValueError, match="clip_norm=None"):
            steps.build_train_step(model, mesh, shape, compressor=comp,
                                   opt=AdamWConfig(), fused_update=True)
    mesh3 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    with mesh3:
        with pytest.raises(ValueError, match="single-pod"):
            steps.build_train_step(model, mesh3, shape, compressor=comp,
                                   opt=opt, fused_update=True)


def test_build_train_step_fused_trains(subproc):
    """End to end: the fused branch compiles, steps, and learns on a tiny
    model (loss strictly decreases over a short run)."""
    out = subproc("""
import functools, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.launch import steps
from repro.models import build_model
from repro.models.config import ShapeSpec
from repro.optim import schedule
from repro.optim.adamw import AdamWConfig
from repro.optim.compress import SketchCompressor
from repro.core.sketch import SketchConfig
from repro.data import DataConfig, SyntheticLM

mesh = jax.make_mesh((1, 1), ("data", "model"))
cfg = reduced(get_config("llama3.2-3b"))
model = build_model(cfg)
shape = ShapeSpec("t", 32, 4, "train")
scfg = SketchConfig(family="tt", k=1024, rank=8, bucket_elems=4*8*16,
                    dims=(4, 8, 16))
comp = SketchCompressor(scfg)
data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4))
with mesh:
    b = steps.build_train_step(
        model, mesh, shape, compressor=comp, opt=AdamWConfig(clip_norm=None),
        lr_fn=functools.partial(schedule.constant, peak_lr=3e-3),
        fused_update=True)
    state = steps.init_train_state(model, jax.random.PRNGKey(0),
                                   compressor=comp)
    losses = []
    for i in range(8):
        state, m = b.fn(state, jax.tree.map(jnp.asarray, data.batch(i)))
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("FUSED_OK first=%.3f last=%.3f" % (losses[0], losses[-1]))
""", timeout=1200)
    assert "FUSED_OK" in out
