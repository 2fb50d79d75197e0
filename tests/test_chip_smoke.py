"""`chip_smoke.py` at a tiny size on the CPU: its phase functions with the
kernels in interpret mode (forced through `rp.force_pallas`), its refusal
to run without a TPU, the four-chip phases on four virtual devices, and the
compile-cache placement it shares with the launch CLIs."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro import rp

from conftest import REPO, SRC

TINY = chip_smoke.Sizes(
    k=128, rank=2, buckets=4, orders=((8, 16, 16), (8, 8, 8, 8)),
    struct_dims=(8, 16, 16), struct_rank=2, struct_batch=16,
    serve_k=128, serve_dims=(8, 16, 16), store_items=600, store_batch=200,
    item_rank=2, requests=12, twins=2, top_m=3, layer_scale=64,
    sketch_dims=(8, 16, 16))


@pytest.mark.parametrize("phase", ["dense", "struct", "serve", "update"])
def test_phase_runs_tiny_in_interpret_mode(phase):
    fn = getattr(chip_smoke, f"phase_{phase}")
    with rp.force_pallas():
        results = fn(TINY, jax.random.PRNGKey(0), interpret_ok=True)
    assert results
    for r in results:
        assert r.max_err <= chip_smoke.TOL
        assert r.name and r.run_s >= 0 and r.compile_s >= 0
    assert any(r.kernel_calls > 0 for r in results)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 2
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_four_chip_phases(subproc):
    out = subproc(f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
import chip_smoke
from repro import rp
from repro.launch.mesh import make_mesh
sz = chip_smoke.Sizes(k=128, rank=2, buckets=8, orders=((8, 16, 16),),
                      layer_scale=64, sketch_dims=(8, 16, 16))
mesh = make_mesh((4,), ("pod",))
with rp.force_pallas():
    res = (chip_smoke.phase_pod_sync(sz, jax.random.PRNGKey(0), mesh,
                                     interpret_ok=True)
           + chip_smoke.phase_project_sharded(sz, jax.random.PRNGKey(0),
                                              mesh, interpret_ok=True))
assert len(res) == 3 and all(r.kernel_calls > 0 for r in res)
print("FOUR_OK", max(r.max_err for r in res))
""", devices=4)
    assert "FOUR_OK" in out


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CacheHits, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
print(path, CacheHits.count)
"""


def _probe(env):
    env = dict(env, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    path, hits = res.stdout.split()[-2:]
    return path, int(hits)


def test_compile_cache_dir_from_env_and_hits_on_rerun(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    path, hits = _probe(env)
    assert path == str(tmp_path) and hits == 0
    assert any(tmp_path.iterdir())
    path2, hits2 = _probe(env)
    assert path2 == str(tmp_path) and hits2 > 0


def test_compile_cache_defaults_into_checkout():
    from repro.launch.compile_cache import DEFAULT_DIR
    assert DEFAULT_DIR == __import__("pathlib").Path(REPO) / ".jax_cache"
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_chip_peaks_keyed_by_device_kind():
    from repro.launch.roofline import chip_peaks
    assert chip_peaks("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no peak table entry"):
        chip_peaks("TPU v9 imaginary")


def test_phase_result_line():
    r = chip_smoke.PhaseResult("a/tt/8x16x16", 1.0, 2.0, 3, 0, 1e-6)
    line = r.line("TPU v5 lite")
    assert line.startswith("phase a/tt/8x16x16: device=TPU v5 lite ")
    assert "kernel_dispatches=3 interpret_dispatches=0" in line
