"""The benchmark's yardstick on the CPU: the trace reduction, the work
counts, and the peak table."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import tracered, work
from bench.peaks import PEAKS, peaks


def _op(name, start, dur, device=0):
    return tracered.Op(name, start, dur, device)


def test_union_and_busy_of_overlapping_ops():
    ops = [_op("a.1", 0.0, 1.0), _op("b.2", 0.5, 1.0), _op("c", 3.0, 0.5),
           _op("a.3", 0.0, 2.0, device=1)]
    assert tracered.union([(0, 1), (0.5, 1.5), (3, 3.5)]) == [(0, 1.5),
                                                              (3, 3.5)]
    # device 0 is busy 2.0 s, device 1 2.0 s: the mean over devices
    assert tracered.busy_seconds(ops) == pytest.approx(2.0)
    assert tracered.seconds_of(ops, ["a"]) == pytest.approx(
        (1.0 + 2.0) / 2)
    assert tracered.seconds_of(ops, ["zzz"]) == 0.0
    clipped = tracered.clip(ops, 0.75, 3.25)
    assert tracered.busy_seconds([o for o in clipped if o.device == 0]) \
        == pytest.approx(0.75 + 0.25)
    gaps = tracered.idle_gaps(ops, 0.0, 4.0, [("bench.tick", 1.4, 3.1)])
    assert gaps[0] == ["bench.tick", pytest.approx(1.5)]
    assert gaps[1] == ["host:unspanned", pytest.approx(0.5)]


def test_names_of_tpu_ops_reduce_to_stable_kernel_names():
    ev = "%sweep_project.1 = f32[8,1024]{1,0} custom-call(f32[8,16384,64])"
    assert tracered.hlo_name(ev) == "sweep_project.1"
    assert tracered.base_name("sweep_project.1") == "sweep_project"
    assert tracered.matches("sweep_project_pipelined.4", ["sweep_project"])
    assert not tracered.matches("copy.3", ["sweep_project"])


def test_reduction_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    assert len(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                         recursive=True)) == 1
    prof = tracered.load(str(tmp_path))
    t0, t1 = tracered.annotation(prof, "bench.window")
    assert t1 > t0
    steps = [s for s in tracered.host_spans(prof, "bench.")
             if s[0] == "bench.step"]
    assert len(steps) == 3
    ops = tracered.clip(tracered.device_ops(prof), t0, t1)
    assert ops, "no device op found in the window"
    busy = tracered.busy_seconds(ops)
    assert 0 < busy <= t1 - t0
    dots = tracered.seconds_of(ops, ["dot"])
    assert 0 < dots <= busy
    assert {o.device for o in ops} == {0}
    names = [n for n, _ in tracered.top_ops(ops)]
    assert any(n.startswith("dot") for n in names)


def test_work_counts_match_hand_counts():
    # TT, dims (2, 3, 4), k = 5, rank 2, one item. Right to left:
    #   last mode: out (2*3) x (5*2), 4 terms each:     2*6*10*4   = 480
    #   mode 1:    out 2 x (5*2), 3*2 terms each:       2*2*10*6   = 240
    #   mode 0:    out 5, 2*2 terms each:               2*5*4      =  40
    w = work.project_dense("tt", (2, 3, 4), 5, 2, 1)
    assert w.flops == 760
    # input 24 + sketch 5 floats; cores (1*2*2 + 2*3*2 + 2*4*1) * 5 floats
    assert w.bytes == 4 * (24 + 5) + 4 * 5 * 24
    assert work.project_dense("tt", (2, 3, 4), 5, 2, 3).flops == 3 * 760
    # CP, same shapes: modes 1 and 0 carry no rank contraction
    #   480 + 2*2*10*3 + 2*1*10*2 = 480 + 120 + 40
    assert work.project_dense("cp", (2, 3, 4), 5, 2, 1).flops == 640
    assert work.operator_bytes("cp", (2, 3, 4), 5, 2) == 4 * 5 * 2 * 9
    # carry sweep, TT operator rank 2 over a TT input of rank 3, dims (4,):
    #   2*k*q*d*r*r + 2*k*r*d*q*q = 2*5*3*4*4 + 2*5*2*4*9 = 480 + 720
    assert work.project_struct("tt", "tt", (4,), 5, 2, 3).flops == 1200
    # ... over a CP input of rank 3: 480 + 2*5*2*4*3 = 480 + 240
    assert work.project_struct("tt", "cp", (4,), 5, 2, 3).flops == 720
    # the roofline's least time and its bound
    t, bound = work.Work(197e12, 1.0).least_seconds(PEAKS["TPU v5 lite"])
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.Work(1.0, 819e9).least_seconds(PEAKS["TPU v5 lite"])
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peak table entry"):
        peaks("cpu")


def test_run_refuses_without_a_tpu():
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "ds67b-tp2.sketch-step", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=root)
    assert res.returncode == 2
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""
