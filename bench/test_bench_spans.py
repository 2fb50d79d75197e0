"""The store's program spans as the benchmark's trace reader sees them, and
the query cell as the benchmark runs it today.

`SketchStore.query` opens `store.query` and, per tile, `store.upload`,
`store.fetch` and `store.merge` through `repro.obs`; with obs on, each also
enters a profiler annotation, so a profile captured around a query holds
them on the device trace's clock, where `tracered.host_spans` finds them.
The harness keeps obs off in both its runs, so its lines stay as they
were."""
import copy

import jax
import numpy as np
import pytest

from bench import harness, tracered
from bench.test_bench_store import run, tiny_cell
from repro import obs, rp
from repro.serve import SketchStore

SPEC = rp.ProjectorSpec(family="tt", k=128, dims=(8, 8, 8), rank=2)
ROWS, TILE = 3000, 1000          # three tiles
NAMES = ("store.upload", "store.fetch", "store.merge")


def _profiled_queries(tmp_path, q, *, enabled, calls=2):
    """Runs `calls` queries of `q` inside a window annotation under the
    profiler; returns the store's spans, by start, and the window."""
    store = SketchStore(SPEC, query_tile=TILE)
    store.add(np.random.default_rng(5).standard_normal(
        (ROWS, SPEC.k)).astype(np.float32))
    store.query(q, 3)                                   # compile outside
    if enabled:
        obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with jax.profiler.TraceAnnotation(harness.WINDOW):
                for _ in range(calls):
                    store.query(q, 3)
    finally:
        if enabled:
            obs.disable()
    prof = tracered.load(str(tmp_path))
    t0, t1 = tracered.annotation(prof, harness.WINDOW)
    spans = sorted(tracered.host_spans(prof, "store."), key=lambda s: s[1])
    return spans, (t0, t1)


def test_store_spans_land_in_the_profile_one_set_per_tile(tmp_path):
    q = np.random.default_rng(6).standard_normal(
        (3, SPEC.k)).astype(np.float32)
    spans, (t0, t1) = _profiled_queries(tmp_path, q, enabled=True)
    queries = [s for s in spans if s[0] == "store.query"]
    assert len(queries) == 2
    assert all(t0 <= s[1] and s[2] <= t1 for s in spans)
    tiles = -(-ROWS // TILE)
    for _, q0, q1 in queries:
        inside = [s[0] for s in spans
                  if s[0] != "store.query" and q0 <= s[1] and s[2] <= q1]
        assert inside == list(NAMES) * tiles
    assert len(spans) == 2 * (1 + len(NAMES) * tiles)


def test_store_spans_are_absent_while_obs_is_off(tmp_path):
    q = np.ones((1, SPEC.k), np.float32)
    spans, _ = _profiled_queries(tmp_path, q, enabled=False)
    assert spans == []


@pytest.mark.parametrize("traced,metrics", [
    (False, {"query_ms", "setup_s"}), (True, {"idle_share.query"})],
    ids=["untraced", "traced"])
def test_query_cell_keeps_obs_off_and_its_metrics(monkeypatch, traced,
                                                  metrics):
    cell = tiny_cell("store1m-tt32.query")
    cell["config"] = copy.deepcopy(cell["config"])
    a = cell["config"]["assumed"]
    a["serve"] = dict(a["serve"], query_tile=TILE)
    calls = []
    real = obs.enable
    monkeypatch.setattr(obs, "enable",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    out = run(cell, traced=traced)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == metrics
    assert calls == [] and not obs.enabled()
