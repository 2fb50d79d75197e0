"""The sketch-step cell at a tiny size on the CPU, through the harness's
own functions (kernels in interpret mode): its comparison passes, and
fails for the control and for each fault the step can have."""
import copy
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.peaks import PEAKS
from bench.systems import sketch_step
from repro import rp

SEED = 2**33 + 7


def tiny_cell():
    cell = harness.load_cell("ds67b-tp2.sketch-step")
    c = copy.deepcopy(cell["config"])
    c.update(hidden_size=64, intermediate_size=256, num_attention_heads=8,
             num_key_value_heads=4, head_dim=8)
    a = c["assumed"]
    a["sketch"] = {"family": "tt", "k": 128, "rank": 2, "dims": [8, 16, 16]}
    # second moments above the gradient's square, as in the full cell
    a["v_lognormal"] = [-15.0, 0.5]
    cell["config"] = c
    return cell


def run(cell, *, traced=False, **kw):
    with rp.force_pallas():
        return harness.measure(cell, SEED, 0.5, traced, time.perf_counter(),
                               jax.devices(),
                               chip_peaks=lambda _: PEAKS["TPU v5 lite"],
                               **kw)


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


def test_step_cell_is_correct_and_reports_its_metrics(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"sketch_step_ms", "setup_s"}
    assert out["metrics"]["sketch_step_ms"]["value"] > 0
    traced = run(cell, traced=True)
    assert traced["correct"]
    assert "idle_share.step" in traced["metrics"]
    assert 0 < traced["device"]["busy_s"] <= traced["device"]["window_s"]
    assert traced["breakdown"]["device_ops"]


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_control_at_lower_precision_is_not_correct(cell, precision):
    out = run(cell, control=precision)
    assert not out["correct"], out["checks"]


def _unchanged(step):
    return lambda state, grads: (state, {})


def _half_batch(step):
    """Half of every leaf's gradient left out."""
    def half(g):
        flat = g.reshape(-1)
        keep = jnp.arange(flat.size) < flat.size // 2
        return jnp.where(keep, flat, 0.0).reshape(g.shape)
    return lambda state, grads: step(state, jax.tree.map(half, grads))


def _altered(step):
    """One parameter altered where the update produces it."""
    def bad(state, grads):
        new, met = step(state, grads)
        wq = new["params"]["attn"]["wq"]
        new["params"]["attn"]["wq"] = wq.at[0, 0].multiply(1.01)
        return new, met
    return bad


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state-unchanged", "half-batch", "altered"])
def test_faults_in_the_timed_step_are_not_correct(cell, fault):
    out = run(cell, build=lambda c, m, s: sketch_step.SketchStep(
        c, m, s, wrap=fault))
    assert not out["correct"], out["checks"]
