"""The unified telemetry layer: tracer spans (nesting, threads, Chrome
export), metrics (histogram math vs a numpy reference, mergeability,
typed errors under python -O), the Thm-1 distortion monitor (flags an
under-sized k, silent at the prescribed k), and the cross-layer wiring —
one serve replay plus one compressed train run landing rp dispatch spans,
serve tick spans, train steps and ckpt saves on ONE exported timeline."""
import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, rp
from repro.obs import (DistortionMonitor, Histogram, MetricsRegistry, Tracer,
                       required_k)


@pytest.fixture(autouse=True)
def _obs_disabled():
    """Every test starts and ends with the module-global session torn
    down — the layer is process-global by design, tests must not leak."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# tracer: spans, nesting, threads, export
# ---------------------------------------------------------------------------

def test_span_nesting_depths_and_attrs():
    tr = Tracer()
    with tr.span("outer", family="tt") as sp:
        with tr.span("inner"):
            pass
        sp.set(backend="pallas")        # attrs can land mid-region
    tr.instant("marker", step=3)
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["args"]["depth"] == 1
    assert "depth" not in by_name["outer"]["args"]       # top level
    assert by_name["outer"]["args"] == {"family": "tt", "backend": "pallas"}
    assert by_name["marker"]["ph"] == "i"
    # spans append at EXIT: inner closes before outer
    assert [e["name"] for e in evs] == ["inner", "outer", "marker"]
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0.0


def test_span_nesting_is_isolated_across_threads():
    """Two threads nest concurrently; each gets its own context-local
    stack (depths never mix) and its own tid lane in the shared buffer."""
    tr = Tracer()
    start = threading.Barrier(2)

    def worker(name):
        start.wait()
        for _ in range(25):
            with tr.span(f"{name}.outer"):
                with tr.span(f"{name}.inner"):
                    time.sleep(0)       # encourage interleaving
    ts = [threading.Thread(target=worker, args=(n,)) for n in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = tr.events()
    assert len(evs) == 100 and tr.open_spans() == 0
    for e in evs:
        want_depth = 1 if e["name"].endswith(".inner") else 0
        assert e["args"].get("depth", 0) == want_depth, e
    tids = {e["tid"] for e in evs}
    assert len(tids) == 2               # one lane per thread
    for name in ("a", "b"):             # each thread's events share a tid
        assert len({e["tid"] for e in evs
                    if e["name"].startswith(name)}) == 1


def test_chrome_export_schema(tmp_path):
    tr = Tracer()
    with tr.span("s", k=128, dims=(4, 8)):
        tr.instant("i")
    path = tmp_path / "trace.json"
    n = tr.export(path)
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert n == len(doc["traceEvents"]) == 2
    assert doc["displayTimeUnit"] == "ms"
    for e in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(e)
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    # events are ts-sorted in the export (instant fired inside the span)
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)
    # attribute coercion: the tuple became a JSON list
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert span["args"]["dims"] == [4, 8]


def test_export_with_open_span_is_typed_error():
    tr = Tracer()
    cm = tr.span("open")
    cm.__enter__()
    with pytest.raises(ValueError, match="unclosed span"):
        tr.to_chrome()
    with pytest.raises(ValueError, match="unclosed span"):
        tr.clear()
    cm.__exit__(None, None, None)
    assert tr.to_chrome()["traceEvents"][0]["name"] == "open"


# ---------------------------------------------------------------------------
# metrics: histogram math, merge, typed errors
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy_within_bucket_width():
    """Bucket-interpolated percentiles vs the numpy reference on the raw
    samples: exact to within the width of the bucket holding the rank."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=4.0, sigma=1.0, size=4000)
    bounds = tuple(float(b) for b in np.geomspace(1.0, 1e4, 40))
    h = Histogram("h", bounds)
    for s in samples:
        h.observe(float(s))
    for p in (10.0, 50.0, 90.0, 99.0):
        ref = float(np.percentile(samples, p))
        got = h.percentile(p)
        i = int(np.searchsorted(bounds, ref))
        lo = 0.0 if i == 0 else bounds[i - 1]
        hi = bounds[min(i, len(bounds) - 1)]
        assert lo - 1e-9 <= got <= hi + 1e-9, (p, got, ref, lo, hi)
    assert h.mean == pytest.approx(float(np.mean(samples)))
    # p=0 interpolates to the lower edge of the first occupied bucket
    first = next(i for i, c in enumerate(h.counts) if c)
    assert h.percentile(0.0) == (0.0 if first == 0 else bounds[first - 1])
    assert Histogram("e", (1.0,)).percentile(50.0) == 0.0   # empty
    # overflow reports the last finite edge (deliberate under-estimate)
    h2 = Histogram("h2", (10.0,))
    h2.observe(1e9)
    assert h2.percentile(99.0) == 10.0


def test_histogram_merge_matches_single_stream():
    bounds = (10.0, 100.0, 1000.0)
    a, b, ref = (Histogram("m", bounds) for _ in range(3))
    rng = np.random.default_rng(1)
    for i, s in enumerate(rng.uniform(1.0, 2000.0, size=500)):
        (a if i % 2 else b).observe(float(s))
        ref.observe(float(s))
    a.merge(b)
    assert a.counts == ref.counts and a.count == ref.count
    assert a.percentile(99.0) == ref.percentile(99.0)
    with pytest.raises(ValueError, match="bounds differ"):
        a.merge(Histogram("m", (5.0, 50.0)))


def test_metrics_registry_typed_errors_and_merge():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(7.5)
    reg.histogram("h", (10.0, 100.0)).observe(42.0)
    reg.event("ev", step=1)
    with pytest.raises(ValueError, match="monotonic"):
        reg.counter("c").inc(-1)
    with pytest.raises(ValueError, match="already registered as"):
        reg.gauge("c")
    with pytest.raises(ValueError, match="different bounds"):
        reg.histogram("h", (1.0, 2.0))
    with pytest.raises(ValueError, match="positive"):
        reg.histogram("neg", (-1.0, 2.0))
    with pytest.raises(ValueError, match="ascending"):
        reg.histogram("asc", (2.0, 1.0))
    other = MetricsRegistry()
    other.counter("c").inc(2)
    other.gauge("g").set(9.0)
    other.histogram("h", (10.0, 100.0)).observe(7.0)
    other.event("ev", step=2)
    reg.merge(other)
    assert reg.counter("c").value == 5
    assert reg.gauge("g").value == 9.0          # last write wins
    assert reg.histogram("h", (10.0, 100.0)).count == 2
    assert [e["step"] for e in reg.events] == [1, 2]


def test_metrics_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h", (10.0,)).observe(3.0)
    reg.event("boom", why="test")
    path = tmp_path / "m.jsonl"
    assert reg.write_jsonl(path) == 3
    rows = obs.read_jsonl(path)
    assert {r["type"] for r in rows} == {"counter", "histogram", "event"}
    hist = next(r for r in rows if r["type"] == "histogram")
    assert {"bounds", "counts", "sum", "count", "p50", "p99"} <= set(hist)


def test_obs_typed_errors_survive_python_O():
    """The export/bounds misuse checks are typed ValueErrors, not asserts
    — they must still fire under python -O."""
    import os
    import subprocess
    import sys
    code = """
from repro.obs import DistortionMonitor, Histogram, Tracer
tr = Tracer()
cm = tr.span("open")
cm.__enter__()
try:
    tr.to_chrome()
except ValueError as e:
    assert "unclosed span" in str(e), e
else:
    raise SystemExit("open-span export not caught under -O")
cm.__exit__(None, None, None)
try:
    Histogram("h", (-1.0, 2.0))
except ValueError as e:
    assert "positive" in str(e), e
else:
    raise SystemExit("negative bounds not caught under -O")
try:
    Histogram("h", (2.0, 1.0))
except ValueError as e:
    assert "ascending" in str(e), e
else:
    raise SystemExit("non-ascending bounds not caught under -O")
try:
    DistortionMonitor(eps=0.0, delta=0.1)
except ValueError as e:
    assert "eps" in str(e), e
else:
    raise SystemExit("bad eps not caught under -O")
print("O_SAFE_OK")
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-O", "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "O_SAFE_OK" in res.stdout, (
        res.stdout, res.stderr)


# ---------------------------------------------------------------------------
# the module-global session + no-op fast path
# ---------------------------------------------------------------------------

def test_disabled_fast_path_returns_shared_noops():
    assert not obs.enabled()
    assert obs.span("x", a=1) is obs.span("y")          # one shared object
    assert obs.counter("c") is obs.histogram("h")
    with obs.span("x") as sp:
        assert sp.set(a=1) is sp
    obs.instant("i")
    obs.event("e")
    obs.counter("c").inc()
    obs.histogram("h").observe(1.0)                      # all inert
    ctx = obs.enable()
    try:
        assert obs.enabled() and obs.get_tracer() is ctx.tracer
        assert obs.span("x") is not obs.span("x")        # real scopes now
        obs.counter("c").inc(2)
        assert ctx.metrics.counter("c").value == 2
    finally:
        assert obs.disable() is ctx
    assert obs.get_context() is None


def test_enable_uses_the_tracer_and_registry_it_is_given():
    tracer, metrics = Tracer(), MetricsRegistry()
    assert len(tracer) == 0                              # empty, so falsy
    ctx = obs.enable(tracer=tracer, metrics=metrics)
    try:
        assert ctx.tracer is tracer and ctx.metrics is metrics
        with obs.span("x"):
            pass
        assert [e["name"] for e in tracer.events()] == ["x"]
    finally:
        obs.disable()


def test_capture_exports_on_exit(tmp_path):
    tp, mp = tmp_path / "t.json", tmp_path / "m.jsonl"
    with obs.capture(trace_path=tp, metrics_path=mp):
        with obs.span("region", tag="x"):
            obs.counter("n").inc()
    assert not obs.enabled()
    assert json.loads(tp.read_text())["traceEvents"][0]["name"] == "region"
    assert obs.read_jsonl(mp)[0]["name"] == "n"


# ---------------------------------------------------------------------------
# distortion monitor vs Thm 1
# ---------------------------------------------------------------------------

def _feed(mon, k, n_samples=256, seed=0):
    """Stream real TT-RP sketch distortions ||Sx||^2/||x||^2 at width k."""
    dims, rank = (4, 8, 8), 2
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=k, dims=dims, rank=rank),
        jax.random.PRNGKey(7))
    xs = jax.random.normal(jax.random.PRNGKey(8),
                           (n_samples, int(np.prod(dims))))
    ys = np.asarray(rp.project(op, xs, backend="xla"))
    xs = np.asarray(xs)
    for i in range(n_samples):
        mon.observe_norms("tt", 3, k, float(xs[i] @ xs[i]),
                          float(ys[i] @ ys[i]), rank=rank)


def test_required_k_matches_chebyshev():
    # tt, N=3, R=2: c = 3(1 + 2/R)^(N-1) - 1 = 11
    assert required_k("tt", 3, rank=2, eps=0.5, delta=0.1) == \
        math.ceil(11 / (0.1 * 0.25)) == 440
    with pytest.raises(ValueError, match="eps"):
        required_k("tt", 3, rank=2, eps=0.0, delta=0.1)


def test_distortion_monitor_flags_undersized_k_only():
    """k=8 (<< the 440 Thm-1 prescribes for eps=0.5, delta=0.1) must
    alert; k=512 (above it) must stay silent on the same stream."""
    alerts = []
    mon = DistortionMonitor(eps=0.5, delta=0.1, min_samples=64,
                            on_alert=alerts.append)
    _feed(mon, k=8)
    assert len(alerts) == 1, "undersized k must alert exactly once"
    al = alerts[0]
    assert (al.family, al.order, al.k) == ("tt", 3, 8)
    assert al.out_rate > al.delta and al.k_required == 440
    ev = al.as_event()
    assert ev["name"] == "distortion.alert" and ev["k"] == 8

    mon2 = DistortionMonitor(eps=0.5, delta=0.1, min_samples=64,
                             on_alert=alerts.append)
    _feed(mon2, k=512)
    assert len(alerts) == 1, "paper-prescribed k must not alert"
    rows = mon2.summary()
    assert len(rows) == 1 and not rows[0]["alerted"]
    assert rows[0]["out_rate"] <= 0.1


def test_distortion_alert_routes_to_metrics_and_trace():
    """enable(distortion=...) auto-wires alerts into the metrics event
    log AND the trace as an instant."""
    ctx = obs.enable(distortion=DistortionMonitor(eps=0.5, delta=0.1,
                                                  min_samples=64))
    try:
        _feed(ctx.distortion, k=8)
    finally:
        obs.disable()
    evs = [e for e in ctx.metrics.events if e["name"] == "distortion.alert"]
    assert len(evs) == 1 and evs[0]["k_required"] == 440
    instants = [e for e in ctx.tracer.events()
                if e["ph"] == "i" and e["name"] == "distortion.alert"]
    assert len(instants) == 1


def test_distortion_monitor_typed_errors():
    with pytest.raises(ValueError, match="eps"):
        DistortionMonitor(eps=-1.0, delta=0.1)
    with pytest.raises(ValueError, match="delta"):
        DistortionMonitor(eps=0.5, delta=1.0)
    with pytest.raises(ValueError, match="min_samples"):
        DistortionMonitor(eps=0.5, delta=0.1, min_samples=0)
    mon = DistortionMonitor(eps=0.5, delta=0.1)
    with pytest.raises(ValueError, match="k"):
        mon.observe("tt", 3, 0, 1.0)


# ---------------------------------------------------------------------------
# cross-layer wiring
# ---------------------------------------------------------------------------

def test_train_loop_straggler_emits_exactly_one_event_per_straggler():
    """The [straggler] log line and the train.straggler event are 1:1 —
    the forced spike at step 8 produces its event, and no step produces
    more than one."""
    from repro.data import DataConfig, SyntheticLM
    from repro.runtime import train_loop

    def step_fn(state, batch):
        time.sleep(0.25 if int(state["step"]) == 8 else 0.02)
        return ({"w": state["w"] + 1.0, "step": state["step"] + 1},
                {"loss": jnp.sum(state["w"])})

    data = SyntheticLM(DataConfig(vocab=16, seq_len=8, global_batch=2))
    logs = []
    ctx = obs.enable()
    try:
        train_loop.run(step_fn, {"w": jnp.zeros(()), "step": jnp.int32(0)},
                       data, train_loop.LoopConfig(total_steps=12),
                       log=logs.append)
    finally:
        obs.disable()
    evs = [e for e in ctx.metrics.events if e["name"] == "train.straggler"]
    log_lines = [l for l in logs if l.startswith("[straggler]")]
    assert len(evs) == len(log_lines)       # routed 1:1, log strings kept
    assert any(e["step"] == 8 and e["zscore"] > 4.0 for e in evs)
    assert len([e for e in evs if e["step"] == 8]) == 1
    # the trace got the same markers as instants, on the step timeline
    spans = [e for e in ctx.tracer.events() if e["name"] == "train.step"]
    assert len(spans) == 12
    assert all(e["args"]["step"] in range(12) for e in spans)


def test_resume_and_fallback_route_through_event_layer(tmp_path):
    """[resume]/[fallback] keep their log strings AND land as events with
    the restored/requested steps attached."""
    from repro.ckpt import checkpointer
    from repro.data import DataConfig, SyntheticLM
    from repro.runtime import train_loop
    from repro.runtime.resilience import flip_byte

    def step_fn(state, batch):
        return {"w": state["w"] + 1.0}, {"loss": jnp.sum(state["w"])}

    data = SyntheticLM(DataConfig(vocab=16, seq_len=8, global_batch=2))
    cfg = train_loop.LoopConfig(total_steps=8, ckpt_dir=str(tmp_path),
                                ckpt_every=2, async_ckpt=False)
    train_loop.run(step_fn, {"w": jnp.zeros(())}, data, cfg,
                   log=lambda s: None)
    flip_byte(f"{tmp_path}/step_{8:010d}/arr_0.npy")   # corrupt newest
    logs = []
    ctx = obs.enable()
    try:
        cfg2 = train_loop.LoopConfig(total_steps=10,
                                     ckpt_dir=str(tmp_path), ckpt_every=5)
        train_loop.run(step_fn, {"w": jnp.zeros(())}, data, cfg2,
                       log=logs.append)
    finally:
        obs.disable()
    assert any(l.startswith("[resume]") for l in logs)  # strings kept
    names = [e["name"] for e in ctx.metrics.events]
    assert names.count("ckpt.fallback") == 1
    assert names.count("ckpt.resume") == 1
    fb = next(e for e in ctx.metrics.events if e["name"] == "ckpt.fallback")
    assert fb["step_requested"] == 8 and fb["step_restored"] == 6
    # the restore span carries the fallback as an attribute
    restores = [e for e in ctx.tracer.events()
                if e["name"] == "ckpt.restore"]
    assert len(restores) == 1
    assert restores[0]["args"]["fallback_from"] == 8
    assert restores[0]["args"]["step"] == 6
    assert checkpointer.latest_step(tmp_path) == 10


def test_shared_timeline_serve_plus_train(tmp_path):
    """The acceptance criterion: ONE enabled session spanning a serve
    replay and an 8-step compressed train run with async checkpoints
    exports a single Perfetto-loadable trace where rp dispatch spans,
    serve tick spans, train steps and ckpt saves share the timeline (ckpt
    saves on the writer thread's own lane), plus parseable JSONL metrics."""
    from repro.core.sketch import SketchConfig
    from repro.data import DataConfig, SyntheticLM
    from repro.optim import AdamWConfig, adamw
    from repro.optim.compress import SketchCompressor
    from repro.runtime import train_loop
    from repro.serve import ServeConfig, SketchServer, replay, synth_trace

    tp, mp = tmp_path / "trace.json", tmp_path / "metrics.jsonl"
    with obs.capture(trace_path=tp, metrics_path=mp) as ctx:
        # -- serve replay -------------------------------------------------
        spec = rp.ProjectorSpec(family="tt", k=128, dims=(4, 8, 8), rank=2)
        srv = SketchServer(ServeConfig(max_batch=4, backend="xla",
                                       ingest=False))
        replay(srv, synth_trace(16, [(spec, 0)], seed=2))
        # -- 8-step sketch-compressed train with async ckpts -------------
        comp = SketchCompressor(SketchConfig(family="tt", k=64, rank=2,
                                             bucket_elems=256,
                                             dims=(4, 8, 8)))
        ocfg = AdamWConfig(clip_norm=1.0)
        params = {"w": jnp.ones((256,))}
        opt = adamw.init_state(params, ocfg)
        ef = comp.init_state(params)

        def step_fn(state, batch):
            g = {"w": jnp.ones((256,)) * 0.01}
            g_hat, new_ef, m = comp.compress(g, state["ef"],
                                             step=int(state["opt"]["count"]))
            p, new_opt, _ = adamw.update(state["params"], g_hat,
                                         state["opt"], 1e-3, ocfg)
            return ({"params": p, "opt": new_opt, "ef": new_ef},
                    {"loss": jnp.sum(p["w"] * p["w"]), **m})

        train_loop.run(step_fn, {"params": params, "opt": opt, "ef": ef},
                       data=SyntheticLM(DataConfig(vocab=16, seq_len=8,
                                                   global_batch=2)),
                       cfg=train_loop.LoopConfig(total_steps=8,
                                                 ckpt_dir=str(tmp_path / "ck"),
                                                 ckpt_every=4),
                       log=lambda s: None)
    doc = json.loads(tp.read_text())
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"rp.project", "serve.tick", "train.step", "ckpt.save"} <= names
    # one pid, ckpt saves on the async writer's OWN lane of that timeline
    assert len({e["pid"] for e in evs}) == 1
    save_tids = {e["tid"] for e in evs if e["name"] == "ckpt.save"}
    step_tids = {e["tid"] for e in evs if e["name"] == "train.step"}
    assert save_tids and save_tids.isdisjoint(step_tids)
    # serve tick spans carry the lane tags; dispatch spans the route tags
    tick = next(e for e in evs if e["name"] == "serve.tick")
    assert {"batch", "family", "k", "structure"} <= set(tick["args"])
    proj = next(e for e in evs if e["name"] == "rp.project")
    assert {"family", "structure", "backend", "pipeline"} <= set(proj["args"])
    rows = obs.read_jsonl(mp)
    assert any(r["type"] == "histogram" and r["name"] == "serve/queue_delay_us"
               for r in rows)
    assert any(r["type"] == "histogram" and r["name"] == "serve/queue_delay_us"
               and r["count"] == 16 for r in rows)
    assert ctx.metrics.histogram("serve/queue_delay_us").count == 16
