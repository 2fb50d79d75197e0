"""Sharded sketching engine: shard_map bucket-axis sharding
(`rp.project_sharded` / `rp.sketch_tree_sharded`), the
`compress_collective` cross-pod compressed all-reduce (numeric equivalence
with the vmap simulation + HLO wire-bytes accounting), and `bucket_pspec`
divisibility. Multi-device cases run in subprocesses with fake XLA devices;
the main process keeps its single CPU device."""
import jax
import jax.numpy as jnp
import pytest

from repro import rp


def test_bucket_pspec_single_device():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    assert rp.bucket_pspec(mesh, 16) == P(("data",))
    assert rp.bucket_pspec(mesh, 16, exclude=("data",))[0] is None


def test_project_sharded_falls_back_without_shardable_axes():
    """A spec that shards over nothing routes through the plain dispatch."""
    mesh = jax.make_mesh((1,), ("data",))
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2),
        jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16, 16))
    y = rp.project_sharded(op, x, mesh=mesh)
    assert y.shape == (4, 128)
    import numpy as np
    np.testing.assert_allclose(np.asarray(y), np.asarray(rp.project(op, x)),
                               rtol=1e-5, atol=1e-5)


def test_bucket_pspec_divisibility(subproc):
    out = subproc("""
import jax
from repro import rp
from jax.sharding import PartitionSpec as P
mesh = jax.make_mesh((2, 4), ("pod", "data"))
assert rp.bucket_pspec(mesh, 8)[0] == ("pod", "data")
assert rp.bucket_pspec(mesh, 2) == P(("pod",))          # largest valid prefix
assert rp.bucket_pspec(mesh, 3)[0] is None              # nothing divides
assert rp.bucket_pspec(mesh, 8, exclude=("pod",)) == P(("data",))
assert rp.bucket_pspec(mesh, 8, axes=("data",)) == P(("data",))
print("PSPEC_OK")
""", devices=8)
    assert "PSPEC_OK" in out


def test_project_sharded_matches_and_single_dispatch(subproc):
    """Sharded == unsharded projection/adjoint; ONE kernel dispatch per
    trace (the shard_map body traces once, each shard replays it)."""
    out = subproc("""
import jax, numpy as np
from repro import rp
mesh = jax.make_mesh((8,), ("data",))
op = rp.make_projector(
    rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2),
    jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 16, 16))
with rp.dispatch_stats() as st, rp.force_pallas():
    y = rp.project_sharded(op, x, mesh=mesh)
assert st.kernel_calls == 1, st.kernel_calls
y_ref = rp.project(op, x, backend="xla")
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
with rp.dispatch_stats() as st, rp.force_pallas():
    xh = rp.reconstruct_sharded(op, y, mesh=mesh)
assert st.kernel_calls == 1, st.kernel_calls
xh_ref = rp.reconstruct(op, y, backend="xla")
np.testing.assert_allclose(np.asarray(xh), np.asarray(xh_ref),
                           rtol=2e-4, atol=2e-4)
# indivisible bucket count is a typed error, not silent replication
try:
    rp.project_sharded(op, x[:6], mesh=mesh,
                       spec=jax.sharding.PartitionSpec(("data",)))
except ValueError as e:
    assert "divisible" in str(e)
else:
    raise AssertionError("expected ValueError")
print("PROJECT_SHARDED_OK")
""", devices=8)
    assert "PROJECT_SHARDED_OK" in out


def test_sketch_tree_sharded_matches_sketcher(subproc):
    """sketch_tree_sharded == PytreeSketcher.sketch under the same key; one
    kernel dispatch per leaf per trace; ragged leaves fall back unsharded
    but stay bit-identical."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro import rp
from repro.core.sketch import PytreeSketcher, SketchConfig
mesh = jax.make_mesh((8,), ("data",))
cfg = SketchConfig(family="tt", k=128, rank=2, bucket_elems=8 * 16 * 16,
                   dims=(8, 16, 16))
tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (16, 2048)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (3000,))}  # ragged: 2 buckets
key = jax.random.PRNGKey(42)
with rp.dispatch_stats() as st, rp.force_pallas():
    y = rp.sketch_tree_sharded(cfg, tree, key, mesh=mesh)
assert st.kernel_calls == 2, st.kernel_calls   # exactly one per leaf
sk = PytreeSketcher(cfg, tree)
y_ref = sk.sketch(tree, key)
assert y.shape == y_ref.shape == (sk.n_buckets, cfg.k)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
print("SKETCH_TREE_OK", sk.n_buckets)
""", devices=8)
    assert "SKETCH_TREE_OK" in out


def test_compress_collective_equals_per_pod(subproc):
    """The shard_map collective == the vmap(spmd_axis_name) simulation to
    fp32 tolerance, both sync modes, on an 8-pod host mesh."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.sketch import SketchConfig
from repro.optim.compress import SketchCompressor

CFG = SketchConfig(family="tt", k=512, rank=4, bucket_elems=4 * 8 * 16,
                   dims=(4, 8, 16))
npod = 8
mesh = jax.make_mesh((npod,), ("pod",))
g = {"w": jax.random.normal(jax.random.PRNGKey(2), (npod, 500)),
     "b": jax.random.normal(jax.random.PRNGKey(3), (npod, 33))}
state = {"residual": jax.tree.map(lambda x: 0.1 * x, g)}
from repro.models import settings as model_settings
for sync in ("sketch-mean", "local-mean"):
    ref = SketchCompressor(CFG, sync=sync).compress_per_pod(g, state, step=0)
    comp = SketchCompressor(CFG, sync=sync, pod_axis="pod")
    # trace with the AMBIENT settings mesh set: the in-body plain sketcher
    # must not emit the legacy global-hint constraint inside the manual
    # region (which would abort XLA), regardless of ambient state
    with model_settings.override(mesh=mesh):
        out = jax.jit(lambda gg, ss, step: comp.compress_collective(
            gg, ss, step=step, mesh=mesh))(g, state, 0)
    for a, b in zip(jax.tree.leaves(ref[:2]), jax.tree.leaves(out[:2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    # wire_bytes metric reports the ACTIVE formulation
    assert float(out[2]["wire_bytes"]) == (
        out[2]["sketch_bytes"] if sync == "sketch-mean"
        else out[2]["dense_bytes"])
# a leading dim that is a LARGER multiple of npod would shard_map cleanly
# but drop every other pod's row — must be a typed error, not silence
half = jax.make_mesh((npod // 2,), ("pod",),
                     devices=jax.devices()[:npod // 2])
try:
    comp.compress_collective(g, state, step=0, mesh=half)
except ValueError as e:
    assert "one row per pod" in str(e), e
else:
    raise AssertionError("expected ValueError for npod mismatch")
print("COLLECTIVE_EQ_OK")
""", devices=8)
    assert "COLLECTIVE_EQ_OK" in out


def test_compress_collective_wire_bytes(subproc):
    """HLO inspection (the acceptance criterion): under sync='sketch-mean'
    the ONLY cross-pod collective is one all-reduce of n_buckets * k floats;
    'local-mean' moves the dense bytes instead. Metrics are dropped from the
    jitted outputs so their telemetry reductions DCE away."""
    out = subproc("""
import jax, numpy as np
from repro.core.sketch import PytreeSketcher, SketchConfig
from repro.launch.roofline import parse_collectives
from repro.optim.compress import SketchCompressor

CFG = SketchConfig(family="tt", k=512, rank=4, bucket_elems=4 * 8 * 16,
                   dims=(4, 8, 16))
npod = 8
mesh = jax.make_mesh((npod,), ("pod",))
g = {"w": jax.random.normal(jax.random.PRNGKey(2), (npod, 1000)),
     "b": jax.random.normal(jax.random.PRNGKey(3), (npod, 33))}
state = {"residual": jax.tree.map(lambda x: 0.1 * x, g)}
sk = PytreeSketcher(CFG, jax.tree.map(lambda x: x[0], g))
for sync in ("sketch-mean", "local-mean"):
    comp = SketchCompressor(CFG, sync=sync, pod_axis="pod")
    f = jax.jit(lambda gg, ss, step: comp.compress_collective(
        gg, ss, step=step, mesh=mesh)[:2])
    txt = f.lower(g, state, 0).compile().as_text()
    coll = parse_collectives(txt)
    kinds = sorted(coll["per_type"])
    assert kinds == ["all-reduce"], kinds   # pmean is the ONLY collective
    ar = coll["per_type"]["all-reduce"]
    if sync == "sketch-mean":
        assert ar["count"] == 1, ar
        assert ar["bytes"] == sk.n_buckets * CFG.k * 4, (
            ar["bytes"], sk.n_buckets, CFG.k)
    else:
        assert ar["bytes"] == sk.dense_bytes(), (ar, sk.dense_bytes())
    print(sync, "bytes", int(ar["bytes"]))
print("WIRE_BYTES_OK")
""", devices=8)
    assert "WIRE_BYTES_OK" in out


def test_train_step_lowers_collective_on_pod_mesh(subproc):
    """build_train_step wires compress_collective: the compiled step on a
    2x2x2 mesh contains a sketch-sized all-reduce when sync='sketch-mean'
    (the model's own collectives live on other channels; we only assert the
    step lowers and runs — numerics are covered by the convergence test)."""
    out = subproc("""
import functools, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.launch import steps
from repro.models import build_model
from repro.models.config import ShapeSpec
from repro.optim import schedule
from repro.optim.compress import SketchCompressor
from repro.core.sketch import SketchConfig
from repro.data import DataConfig, SyntheticLM

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced(get_config("llama3.2-3b"))
model = build_model(cfg)
shape = ShapeSpec("t", 32, 8, "train")
scfg = SketchConfig(family="tt", k=1024, rank=8, bucket_elems=4 * 8 * 16,
                    dims=(4, 8, 16))
comp = SketchCompressor(scfg, sync="sketch-mean")
data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))
with mesh:
    b = steps.build_train_step(model, mesh, shape, compressor=comp,
        lr_fn=functools.partial(schedule.constant, peak_lr=3e-3))
    compiled = b.fn.lower(*b.args).compile()
    state = steps.init_train_state(model, jax.random.PRNGKey(0),
                                   compressor=comp, npod=2)
    state, m = b.fn(state, jax.tree.map(jnp.asarray, data.batch(0)))
assert float(m["loss"]) > 0 and float(m["wire_bytes"]) > 0
print("TRAIN_COLLECTIVE_OK", int(m["wire_bytes"]))
""", devices=8, timeout=1200)
    assert "TRAIN_COLLECTIVE_OK" in out


def test_sketcher_explicit_mesh_constrains_buckets():
    """PytreeSketcher(mesh=, bucket_spec=) pins the bucket layout without
    consulting the global settings hint; indivisible leaves fall back."""
    from jax.sharding import PartitionSpec as P
    from repro.core.sketch import PytreeSketcher, SketchConfig
    mesh = jax.make_mesh((1,), ("data",))
    cfg = SketchConfig(family="tt", k=64, rank=2, bucket_elems=4 * 8 * 16,
                       dims=(4, 8, 16))
    tree = {"w": jnp.zeros((4, 512))}
    sk = PytreeSketcher(cfg, tree, mesh=mesh, bucket_spec=P(("data",)))
    y = sk.sketch(tree, jax.random.PRNGKey(0))
    assert y.shape == (4, 64)
    rec = sk.unsketch(y, jax.random.PRNGKey(0))
    assert rec["w"].shape == (4, 512)


@pytest.mark.parametrize("bad_model", [3, 0, -1])
def test_make_host_mesh_rejects_bad_model(bad_model):
    from repro.launch.mesh import make_host_mesh
    if bad_model == 3 and len(jax.devices()) % 3 == 0:
        pytest.skip("3 divides the device count here")
    with pytest.raises(ValueError, match="divisor"):
        make_host_mesh(model=bad_model)
