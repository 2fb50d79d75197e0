"""The sketch-serving engine end to end: project_many fan-out, rank-ragged
coalescing, the dynamic batcher's flush policy, the LRU operator cache,
the JL similarity store, the trace replayer's acceptance criteria, and the
SlotServer batched-prefill equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rp
from repro.core.formats import (pad_cp_rank, pad_tt_rank, random_cp,
                                random_tt, stack_ragged_cp, stack_ragged_tt)
from repro.serve import (DynamicBatcher, OperatorCache, ServeConfig,
                         SketchRequest, SketchServer, SketchStore, replay,
                         synth_trace)

SPEC = rp.ProjectorSpec(family="tt", k=128, dims=(4, 8, 8), rank=2)
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# rank-ragged coalescing (core.formats)
# ---------------------------------------------------------------------------

def test_pad_tt_rank_is_exact():
    t = random_tt(KEY, (4, 6, 5), 2)
    padded = pad_tt_rank(t, (1, 5, 4, 1))
    assert padded.ranks == (1, 5, 4, 1)
    np.testing.assert_allclose(np.asarray(padded.full()),
                               np.asarray(t.full()), rtol=1e-6)


def test_pad_tt_rank_rejects_boundary_and_shrink():
    t = random_tt(KEY, (4, 6, 5), 3)
    with pytest.raises(ValueError, match="boundary"):
        pad_tt_rank(t, (2, 4, 4, 1))
    with pytest.raises(ValueError, match="below current"):
        pad_tt_rank(t, (1, 2, 4, 1))
    with pytest.raises(ValueError, match="length"):
        pad_tt_rank(t, (1, 4, 1))


def test_pad_cp_rank_is_exact():
    t = random_cp(KEY, (4, 6, 5), 2)
    padded = pad_cp_rank(t, 6)
    assert padded.rank == 6
    np.testing.assert_allclose(np.asarray(padded.full()),
                               np.asarray(t.full()), rtol=1e-6)
    with pytest.raises(ValueError, match="below current"):
        pad_cp_rank(t, 1)


def test_stack_ragged_tt_preserves_each_item():
    ts = [random_tt(jax.random.fold_in(KEY, i), (4, 6, 5), r)
          for i, r in enumerate((2, 4, 3))]
    xb = stack_ragged_tt(ts)
    assert xb.batch == 3 and xb.ranks == (1, 4, 4, 1)
    full = np.asarray(xb.full())
    for i, t in enumerate(ts):
        np.testing.assert_allclose(full[i], np.asarray(t.full()), rtol=1e-5)
    with pytest.raises(ValueError, match="mismatched"):
        stack_ragged_tt([ts[0], random_tt(KEY, (4, 6, 4), 2)])


def test_stack_ragged_cp_mixes_weighted_and_unweighted():
    a = random_cp(jax.random.fold_in(KEY, 0), (4, 6, 5), 2)
    w = jnp.asarray([2.0, 0.5, 1.5])
    b_t = random_cp(jax.random.fold_in(KEY, 1), (4, 6, 5), 3)
    b_t = type(b_t)(b_t.factors, w)
    xb = stack_ragged_cp([a, b_t])
    assert xb.batch == 2 and xb.rank == 3 and xb.weights is not None
    full = np.asarray(xb.full())
    np.testing.assert_allclose(full[0], np.asarray(a.full()), rtol=1e-5)
    np.testing.assert_allclose(full[1], np.asarray(b_t.full()), rtol=1e-5)


# ---------------------------------------------------------------------------
# project_many (rp fan-out entry)
# ---------------------------------------------------------------------------

def test_project_many_matches_per_item_project():
    op = rp.make_projector(SPEC, KEY)
    inputs = [
        jax.random.normal(jax.random.fold_in(KEY, 1), SPEC.dims),
        jax.random.normal(jax.random.fold_in(KEY, 2), (100,)),  # short flat
        random_tt(jax.random.fold_in(KEY, 3), SPEC.dims, 2),
        random_tt(jax.random.fold_in(KEY, 4), SPEC.dims, 4),    # ragged rank
        random_cp(jax.random.fold_in(KEY, 5), SPEC.dims, 3),
    ]
    ys = rp.project_many(op, inputs)
    assert ys.shape == (5, SPEC.k)
    for i, x in enumerate(inputs):
        ref = rp.project(op, x)
        np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(ref),
                                   rtol=2e-4, atol=1e-5)


def test_project_many_one_dispatch_per_structure_group():
    # MXU-aligned spec (k % 128 == 0, dims % 8 == 0): force_pallas only
    # routes aligned shapes to the kernels, and only kernel dispatches
    # are counted by dispatch_stats.
    spec = rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2)
    op = rp.make_projector(spec, KEY)
    tts = [random_tt(jax.random.fold_in(KEY, i), spec.dims, 2 + i % 2)
           for i in range(4)]
    with rp.dispatch_stats() as st, rp.force_pallas():
        rp.project_many(op, tts)                      # homogeneous lane
    assert st.kernel_calls == 1
    mixed = [tts[0], random_cp(KEY, spec.dims, 2),
             jax.random.normal(KEY, spec.dims)]
    with rp.dispatch_stats() as st, rp.force_pallas():
        rp.project_many(op, mixed)
    assert st.kernel_calls == 3                       # one per structure


def test_project_many_rejects_batched_and_oversize():
    op = rp.make_projector(SPEC, KEY)
    tts = [random_tt(KEY, SPEC.dims, 2)] * 2
    batched = stack_ragged_tt(tts)
    with pytest.raises(rp.FormatMismatchError, match="Batched"):
        rp.project_many(op, [batched])
    too_big = jax.random.normal(KEY, (2, SPEC.input_size))
    with pytest.raises(rp.FormatMismatchError, match="one payload"):
        rp.project_many(op, [too_big])
    assert rp.project_many(op, []).shape == (0, SPEC.k)


# ---------------------------------------------------------------------------
# ServeConfig / store typed errors (and python -O survival)
# ---------------------------------------------------------------------------

def test_serve_config_typed_errors():
    with pytest.raises(ValueError, match="flush window"):
        ServeConfig(flush_us=0.0)
    with pytest.raises(ValueError, match="flush window"):
        ServeConfig(flush_us=-5.0)
    with pytest.raises(ValueError, match="max_batch"):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError, match="backend"):
        ServeConfig(backend="tpu")
    with pytest.raises(ValueError, match="cache_capacity"):
        ServeConfig(cache_capacity=0)
    with pytest.raises(ValueError, match="delta"):
        ServeConfig(delta=1.5)
    with pytest.raises(ValueError, match="stats_window"):
        ServeConfig(stats_window=0)


def test_store_typed_errors():
    store = SketchStore(SPEC)
    with pytest.raises(ValueError, match="empty store"):
        store.query(np.zeros(SPEC.k, np.float32), 1)
    store.add(np.zeros((4, SPEC.k), np.float32))
    with pytest.raises(ValueError, match="top_m"):
        store.query(np.zeros(SPEC.k, np.float32), 5)   # > store size
    with pytest.raises(ValueError, match="top_m"):
        store.query(np.zeros(SPEC.k, np.float32), 0)
    with pytest.raises(ValueError, match="mixed-dtype"):
        store.add(np.zeros((1, SPEC.k), np.float64))
    with pytest.raises(ValueError, match="out of range"):
        store.pairwise([0], [7])
    with pytest.raises(ValueError, match="k ="):
        store.query(np.zeros(SPEC.k + 1, np.float32), 1)


def test_serve_errors_survive_python_O():
    """The config/store misuse checks are typed ValueErrors, not asserts —
    they must still fire under python -O."""
    import os
    import subprocess
    import sys
    code = """
import numpy as np
from repro.serve import ServeConfig, SketchStore
from repro.rp import ProjectorSpec
try:
    ServeConfig(flush_us=0.0)
except ValueError as e:
    assert "flush window" in str(e), e
else:
    raise SystemExit("flush_us=0 not caught under -O")
spec = ProjectorSpec(family="tt", k=64, dims=(4, 8), rank=2)
store = SketchStore(spec)
store.add(np.zeros((2, 64), np.float32))
try:
    store.query(np.zeros(64, np.float32), 3)
except ValueError as e:
    assert "top_m" in str(e), e
else:
    raise SystemExit("top_m overflow not caught under -O")
try:
    store.add(np.zeros((1, 64), np.float64))
except ValueError as e:
    assert "mixed-dtype" in str(e), e
else:
    raise SystemExit("mixed-dtype ingest not caught under -O")
print("O_SAFE_OK")
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-O", "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "O_SAFE_OK" in res.stdout, (
        res.stdout, res.stderr)


# ---------------------------------------------------------------------------
# operator cache
# ---------------------------------------------------------------------------

def test_cache_hit_iff_every_spec_field_and_seed_match():
    cache = OperatorCache(capacity=32)
    base = dict(family="tt", k=128, dims=(4, 8, 8), rank=2)
    cache.get(rp.ProjectorSpec(**base), seed=0)
    assert cache.stats.misses == 1
    cache.get(rp.ProjectorSpec(**base), seed=0)
    assert cache.stats.hits == 1                      # identical spec: hit
    variants = [
        dict(base, family="cp"),
        dict(base, k=256),
        dict(base, dims=(8, 4, 8)),
        dict(base, rank=3),
        dict(base, dtype=jnp.bfloat16),
        dict(base, backend="xla"),
    ]
    for i, kw in enumerate(variants):
        cache.get(rp.ProjectorSpec(**kw), seed=0)
        assert cache.stats.misses == 2 + i, kw        # every field keys
    cache.get(rp.ProjectorSpec(**base), seed=7)       # seed keys too
    assert cache.stats.misses == 2 + len(variants)
    assert cache.stats.hits == 1


def test_cache_lru_eviction_order():
    cache = OperatorCache(capacity=2)
    a = rp.ProjectorSpec(family="tt", k=64, dims=(4, 8), rank=2)
    b = rp.ProjectorSpec(family="tt", k=64, dims=(4, 8), rank=3)
    c = rp.ProjectorSpec(family="tt", k=64, dims=(4, 8), rank=4)
    cache.get(a)
    cache.get(b)
    cache.get(a)                   # refresh a: b is now least-recent
    cache.get(c)                   # evicts b
    assert cache.stats.evictions == 1
    assert (a, 0) in cache and (c, 0) in cache and (b, 0) not in cache
    assert [k[0] for k in cache.keys()] == [a, c]     # LRU-first ordering


def test_cache_regenerates_bitwise_identical_after_eviction():
    cache = OperatorCache(capacity=1)
    a = rp.ProjectorSpec(family="tt", k=64, dims=(4, 8), rank=2)
    b = rp.ProjectorSpec(family="cp", k=64, dims=(4, 8), rank=2)
    x = jax.random.normal(KEY, (4, 8))
    y_first = np.asarray(rp.project(cache.get(a, seed=3), x))
    cache.get(b)                                      # evicts a
    assert (a, 3) not in cache
    y_again = np.asarray(rp.project(cache.get(a, seed=3), x))
    assert cache.stats.evictions >= 1
    np.testing.assert_array_equal(y_first, y_again)   # bitwise


# ---------------------------------------------------------------------------
# dynamic batcher flush policy
# ---------------------------------------------------------------------------

def _req(rid, payload, t, spec=SPEC, seed=0):
    return SketchRequest(rid=rid, payload=payload, spec=spec, seed=seed,
                         t_submit=t)


def test_batcher_max_batch_flush():
    cfg = ServeConfig(max_batch=3, flush_us=1e9)
    bat = DynamicBatcher(cfg)
    x = np.zeros(SPEC.dims, np.float32)
    for i in range(3):
        bat.submit(_req(i, x, t=float(i)))
    assert bat.ready(now=2.0)                         # full, age irrelevant
    key, batch = bat.next_batch(now=2.0)
    assert [r.rid for r in batch] == [0, 1, 2]
    assert bat.pending() == 0 and bat.lanes() == 0


def test_batcher_latency_flush_at_exact_deadline():
    """Regression: readiness must use the SAME float expression as
    next_deadline (t_submit + flush_us); computing `now - t_submit >=
    flush_us` can round the other way and spin the replay loop forever."""
    cfg = ServeConfig(max_batch=64, flush_us=1000.0)
    bat = DynamicBatcher(cfg)
    x = np.zeros(SPEC.dims, np.float32)
    t0 = 3337.3333333333335                           # adversarial float
    bat.submit(_req(0, x, t=t0))
    deadline = bat.next_deadline()
    assert deadline == t0 + 1000.0
    assert not bat.ready(now=deadline - 1e-6)
    assert bat.ready(now=deadline)                    # ready AT deadline
    got = bat.next_batch(now=deadline)
    assert got is not None and len(got[1]) == 1


def test_batcher_lanes_split_by_structure_and_seed():
    cfg = ServeConfig(max_batch=8, flush_us=1000.0)
    bat = DynamicBatcher(cfg)
    bat.submit(_req(0, np.zeros(SPEC.dims, np.float32), t=0.0))
    bat.submit(_req(1, random_tt(KEY, SPEC.dims, 2), t=0.0))
    bat.submit(_req(2, random_cp(KEY, SPEC.dims, 2), t=0.0))
    bat.submit(_req(3, np.zeros(SPEC.dims, np.float32), t=0.0, seed=1))
    assert bat.lanes() == 4
    # FIFO across lanes; fullness breaks the four-way t_submit tie
    bat.submit(_req(4, np.zeros(SPEC.dims, np.float32), t=1.0))
    key, batch = bat.next_batch(now=1e6, force=True)
    assert key.structure == "dense" and len(batch) == 2


def test_batcher_force_flush_and_empty():
    cfg = ServeConfig(max_batch=8, flush_us=1e9)
    bat = DynamicBatcher(cfg)
    assert bat.next_batch(now=0.0, force=True) is None
    assert bat.next_deadline() is None
    bat.submit(_req(0, np.zeros(SPEC.dims, np.float32), t=0.0))
    assert not bat.ready(now=10.0)
    assert bat.next_batch(now=10.0) is None           # not ready, no force
    got = bat.next_batch(now=10.0, force=True)        # drain path
    assert got is not None and len(got[1]) == 1


# ---------------------------------------------------------------------------
# the serving engine (acceptance criteria)
# ---------------------------------------------------------------------------

def test_mixed_trace_one_dispatch_per_tick():
    """>= 64 mixed dense/TT/CP requests complete, with exactly ONE
    rp.project dispatch per batcher tick (rp.dispatch_stats-asserted)."""
    spec = rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2)
    server = SketchServer(ServeConfig(max_batch=8, flush_us=1000.0),
                          SketchStore(spec))
    trace = synth_trace(64, [(spec, 0)], seed=3)
    with rp.dispatch_stats() as st, rp.force_pallas():
        rep = replay(server, trace)
    assert rep["requests_done"] == 64 and rep["pending"] == 0
    assert st.kernel_calls == rep["ticks"] > 0
    assert all(r.done and r.sketch.shape == (spec.k,) for r in server.done)
    assert all(r.payload is None for r in server.done)  # originals dropped
    assert rep["store_size"] == 64                      # everything ingested
    # flush policy bounds every queueing latency by the flush window
    assert 0.0 < rep["p50_us"] <= rep["p99_us"] <= 1000.0 + 1e-6
    assert 0.0 < rep["occupancy_mean"] <= 1.0


def test_repeated_spec_trace_cache_hit_rate():
    """Acceptance: >= 90% operator-cache hit rate on a repeated-spec
    trace."""
    server = SketchServer(ServeConfig(max_batch=4, flush_us=500.0))
    trace = synth_trace(96, [(SPEC, 0)], mix=(1.0, 0.0, 0.0), seed=5)
    rep = replay(server, trace)
    assert rep["requests_done"] == 96
    assert rep["cache"]["misses"] == 1
    assert rep["cache"]["hit_rate"] >= 0.9


def test_stats_percentiles_are_windowed():
    """Two-phase trace: a long fast prefix then a slow tail. All-time
    percentiles mask the tail entirely — 4 slow requests after 400 fast
    ones sit above the all-time p99 rank, so it still reads 'fast' — while
    the windowed p50/p99 (last `stats_window` requests) must surface it.
    This is the regression the window exists to catch."""
    cfg = ServeConfig(max_batch=1, flush_us=100.0, backend="xla",
                      ingest=False, stats_window=32)
    srv = SketchServer(cfg)
    x = jax.random.normal(KEY, SPEC.dims)
    t = 0.0
    for _ in range(400):                  # healthy prefix: 100us latency
        srv.submit(x, SPEC, now=t)
        srv.tick(t + 100.0)
        t += 200.0
    for _ in range(4):                    # regressed tail: 50ms latency
        srv.submit(x, SPEC, now=t)
        srv.tick(t + 50_000.0)
        t += 60_000.0
    st = srv.stats()
    all_time = np.percentile([r.latency_us for r in srv.done], 99)
    assert all_time <= 150.0              # the masking, demonstrated
    assert st["stats_window"] == 32 and st["stats_window_n"] == 32
    assert st["p99_us"] >= 10_000.0       # the window sees the slow phase
    assert st["p50_us"] <= 150.0          # but is not all-slow either
    assert st["requests_done"] == 404


def test_engine_submit_validates_structured_dims():
    server = SketchServer(ServeConfig())
    bad = random_tt(KEY, (4, 8, 4), 2)                # != SPEC.dims
    with pytest.raises(rp.FormatMismatchError, match="dims"):
        server.submit(bad, SPEC)
    with pytest.raises(ValueError, match="no sketch store"):
        server.query(np.zeros(SPEC.k, np.float32), 1)
    with pytest.raises(ValueError, match="no sketch store"):
        server.pairwise([0], [0])


def test_store_spec_gates_ingestion():
    other = rp.ProjectorSpec(family="tt", k=128, dims=(4, 8, 8), rank=3)
    server = SketchServer(ServeConfig(max_batch=2, flush_us=10.0),
                          SketchStore(SPEC))
    x = np.ones(SPEC.dims, np.float32)
    server.submit(x, SPEC, now=0.0)
    server.submit(x, other, now=0.0)
    server.drain(0.0)
    assert len(server.store) == 1                     # only SPEC ingested
    matching = [r for r in server.done if r.spec == SPEC]
    assert matching[0].store_id == 0
    assert [r.store_id for r in server.done if r.spec == other] == [None]


# ---------------------------------------------------------------------------
# JL similarity retrieval vs exact dense distances
# ---------------------------------------------------------------------------

def test_query_top_m_within_thm1_bound_of_exact_distances():
    """Seeded acceptance: the similarity endpoint's top-m answers agree
    with exact dense distances to within the Thm-1 distortion bound."""
    spec = rp.ProjectorSpec(family="tt", k=512, dims=(4, 8, 8), rank=2)
    op = rp.make_projector(spec, jax.random.PRNGKey(1))
    n, m = 40, 5
    xs = [jax.random.normal(jax.random.fold_in(KEY, i), spec.dims)
          for i in range(n)]
    store = SketchStore(spec, query_tile=7)           # force multi-tile
    ys = rp.project_many(op, xs)
    store.add(np.asarray(ys))
    dense = np.stack([np.asarray(x).ravel() for x in xs])
    res = store.query(np.asarray(ys[:3]), m, delta=0.05)
    assert res.ids.shape == res.dist2.shape == (3, m)
    assert res.eps == pytest.approx(store.eps_bound(0.05))
    sk = np.asarray(store.get(np.arange(n)), np.float64)
    for qi in range(3):
        # endpoint == brute force over the same sketches, in order
        d2_all = ((sk - sk[qi]) ** 2).sum(1)
        np.testing.assert_array_equal(
            np.sort(res.ids[qi]), np.sort(np.argsort(d2_all,
                                                     kind="stable")[:m]))
        np.testing.assert_allclose(res.dist2[qi], np.sort(d2_all)[:m],
                                   rtol=1e-4, atol=1e-3)
        # each reported distance estimates the TRUE dense distance within
        # the Thm-1 relative-error bound (self-match excluded: d2 = 0)
        for j in range(m):
            sid = int(res.ids[qi][j])
            if sid == qi:
                assert res.dist2[qi][j] < 1e-3
                continue
            true_d2 = float(((dense[qi] - dense[sid]) ** 2).sum())
            assert abs(res.dist2[qi][j] - true_d2) <= res.eps * true_d2
            assert res.dist2_lo[qi][j] <= true_d2
            if np.isfinite(res.dist2_hi[qi][j]):
                assert true_d2 <= res.dist2_hi[qi][j]


def test_query_tiling_is_transparent():
    store_a = SketchStore(SPEC, query_tile=3)
    store_b = SketchStore(SPEC, query_tile=4096)
    rng = np.random.default_rng(0)
    sk = rng.standard_normal((33, SPEC.k)).astype(np.float32)
    store_a.add(sk)
    store_b.add(sk)
    q = sk[:2]
    ra, rb = store_a.query(q, 7), store_b.query(q, 7)
    np.testing.assert_array_equal(ra.ids, rb.ids)
    np.testing.assert_allclose(ra.dist2, rb.dist2, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("nq", [1, 3])
def test_query_answers_the_same_bitwise_with_obs_on_and_off(nq):
    from repro import obs
    store = SketchStore(SPEC, query_tile=10)
    rng = np.random.default_rng(2)
    store.add(rng.standard_normal((33, SPEC.k)).astype(np.float32))
    q = rng.standard_normal((nq, SPEC.k)).astype(np.float32)
    off = store.query(q, 5)
    ctx = obs.enable()
    try:
        on = store.query(q, 5)
    finally:
        obs.disable()
    np.testing.assert_array_equal(on.ids, off.ids)
    assert on.dist2.tobytes() == off.dist2.tobytes()
    # the rows stay on the device: the query sent its own rows alone
    assert ctx.metrics.counter("store/h2d_bytes").value == 4 * SPEC.k * nq


def test_query_spans_nest_one_set_per_tile_under_the_query():
    """One query, one device program: `store.query` holds the program's
    dispatch (`store.sweep`) and the copy back of its answer
    (`store.fetch`), in that order, however many tiles the rows span."""
    from repro import obs
    store = SketchStore(SPEC, query_tile=10)
    store.add(np.random.default_rng(3).standard_normal(
        (33, SPEC.k)).astype(np.float32))
    q = store.get([0, 1])
    with obs.capture() as ctx:
        store.query(q, 3)
    evs = sorted(ctx.tracer.to_chrome()["traceEvents"],
                 key=lambda e: e["ts"])
    (top,) = [e for e in evs if e["name"] == "store.query"]
    assert top["args"] == {"rows": 33, "tiles": 4, "batch": 2, "top_m": 3}
    inner = [e for e in evs if e is not top]
    assert [e["name"] for e in inner] == ["store.sweep", "store.fetch"]
    for e in inner:
        assert e["args"] == {"depth": 1}          # no attributes of its own
        assert e["tid"] == top["tid"]
        assert top["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= top["ts"] + top["dur"]
    assert inner[0]["ts"] + inner[0]["dur"] <= inner[1]["ts"]


def _brute_top(rows, q, m):
    """Float64 brute force: ids of the m least squared distances, and
    those distances, per query row."""
    d2 = ((q.astype(np.float64)[:, None] - rows.astype(np.float64)[None])
          ** 2).sum(-1)
    ids = np.argsort(d2, axis=1, kind="stable")[:, :m]
    return ids, np.take_along_axis(d2, ids, axis=1)


@pytest.mark.parametrize("tile", [7, 64, 4096])
def test_device_query_matches_float64_brute_force_after_growth(tile):
    """Rows that fill no whole tile, in a store that grew several times:
    the device program's top-m is the float64 brute force's."""
    from repro import obs
    store = SketchStore(SPEC, query_tile=tile)
    rng = np.random.default_rng(11)
    # JL sketches of unit-norm items: rows of norm ~1, so float32's
    # rounding of ||q||^2 - 2 q.x + ||x||^2 stays near 1e-7
    rows = (rng.standard_normal((2500, SPEC.k))
            / np.sqrt(SPEC.k)).astype(np.float32)
    with obs.capture() as ctx:
        for lo, hi in [(0, 700), (700, 1100), (1100, 2100), (2100, 2500)]:
            store.add(rows[lo:hi])                # 1024 -> 2048 -> 4096
    assert ctx.metrics.counter("store/grows").value == 2
    assert 2500 % tile
    q = rows[[3, 1500, 2499]] + np.float32(0.01) * rng.standard_normal(
        (3, SPEC.k)).astype(np.float32)
    res = store.query(q, 9)
    want_ids, want_d2 = _brute_top(rows, q, 9)
    np.testing.assert_array_equal(res.ids, want_ids)
    np.testing.assert_allclose(res.dist2, want_d2, rtol=1e-5, atol=1e-6)
    assert res.ids[:, 0].tolist() == [3, 1500, 2499]


@pytest.mark.parametrize("tile", [3, 10, 4096])
def test_query_for_every_row_returns_each_id_once_and_no_empty_row(tile):
    """`top_m == len(store)`: every stored id comes back once, in
    ascending distance, and no row past the last stored one (the
    capacity's padding reads +inf) is ever returned."""
    store = SketchStore(SPEC, query_tile=tile)
    rng = np.random.default_rng(12)
    rows = (rng.standard_normal((33, SPEC.k))
            / np.sqrt(SPEC.k)).astype(np.float32)
    store.add(rows)
    q = (rng.standard_normal((2, SPEC.k)) / np.sqrt(SPEC.k)).astype(
        np.float32)
    res = store.query(q, 33)
    for i in range(2):
        assert sorted(res.ids[i].tolist()) == list(range(33))
        assert np.all(np.diff(res.dist2[i]) >= 0)
        assert np.all(np.isfinite(res.dist2[i]))
    want_ids, want_d2 = _brute_top(rows, q, 33)
    np.testing.assert_array_equal(res.ids, want_ids)
    np.testing.assert_allclose(res.dist2, want_d2, rtol=1e-5, atol=1e-6)


def test_add_after_a_query_is_retrievable_without_a_new_query_program():
    """The live row count is an input of the query program, not a shape:
    rows added within the capacity are found by the program already
    compiled."""
    from repro.serve import store as store_mod
    store = SketchStore(SPEC, query_tile=10)
    rng = np.random.default_rng(13)
    store.add(rng.standard_normal((40, SPEC.k)).astype(np.float32))
    q = rng.standard_normal((1, SPEC.k)).astype(np.float32)
    store.query(q, 5)
    compiled = store_mod._nearest._cache_size()
    new = rng.standard_normal((25, SPEC.k)).astype(np.float32)
    ids = store.add(new)
    assert ids.tolist() == list(range(40, 65))
    for j in (0, 24):
        res = store.query(new[j], 5)
        assert res.ids[0] == 40 + j and res.dist2[0] < 1e-4
    assert store.query(q, 5).ids.shape == (1, 5)
    assert store_mod._nearest._cache_size() == compiled
    np.testing.assert_array_equal(store.get([40, 64]), new[[0, 24]])


def test_device_rows_cross_no_bytes_and_each_doubling_counts():
    """A device array is written on the device: `store/h2d_bytes` counts
    host rows alone. `store/grows` counts each reallocation of a store
    that held rows (the first allocation copies nothing)."""
    from repro import obs
    rng = np.random.default_rng(14)
    host = rng.standard_normal((1024, SPEC.k)).astype(np.float32)
    store = SketchStore(SPEC)
    with obs.capture() as ctx:
        store.add(jnp.asarray(host))              # allocates 1024 rows
        assert ctx.metrics.counter("store/h2d_bytes").value == 0
        assert ctx.metrics.counter("store/grows").value == 0
        store.add(jnp.asarray(host[:1]))          # 1024 -> 2048
        store.add(jnp.asarray(host[1:]))          # fits
        assert ctx.metrics.counter("store/grows").value == 1
        store.add(jnp.asarray(host[:2]))          # 2048 -> 4096
        assert ctx.metrics.counter("store/grows").value == 2
        assert ctx.metrics.counter("store/h2d_bytes").value == 0
        store.add(host[:3])                       # host rows are sent
        assert ctx.metrics.counter("store/h2d_bytes").value == \
            3 * SPEC.k * 4
    assert len(store) == 2053
    np.testing.assert_array_equal(store.get([0, 1024, 2050, 2052]),
                                  host[[0, 0, 0, 2]])
    with pytest.raises(ValueError, match="mixed-dtype"):
        store.add(jnp.asarray(host[:1], jnp.bfloat16))


def test_pairwise_matches_stored_sketch_distances():
    store = SketchStore(SPEC)
    rng = np.random.default_rng(1)
    sk = rng.standard_normal((8, SPEC.k)).astype(np.float32)
    store.add(sk)
    res = store.pairwise([0, 1, 2], [3, 4, 5], delta=0.1)
    want = ((sk[[0, 1, 2]] - sk[[3, 4, 5]]) ** 2).sum(1)
    np.testing.assert_allclose(res.dist2, want, rtol=1e-5)
    assert res.eps == pytest.approx(store.eps_bound(0.1))
    assert (res.dist2_lo <= res.dist2 + 1e-9).all()


# ---------------------------------------------------------------------------
# SlotServer batched prefill (launch.serve satellite)
# ---------------------------------------------------------------------------

def test_slot_server_batched_prefill_matches_token_loop():
    """The batched whole-prompt prefill must reproduce the old
    token-by-token decode-path prefill: same greedy tokens, same
    positions, for every request. Both paths run the same jitted step
    executable, so this is bitwise identity, not tolerance-based."""
    from repro.configs import get_config, reduced
    from repro.launch.serve import Request, SlotServer
    from repro.models import build_model

    cfg = reduced(get_config("llama3.2-3b"))
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=(6 + i % 3,))
               for i in range(4)]

    def run(feed_loop):
        srv = SlotServer(model, slots=2, max_seq=32, eos=None, max_gen=5)
        if feed_loop:
            def _loop_feed(slot, req):
                logits = None
                for t in req.prompt:
                    tok = srv.cur_tok.copy()
                    tok[slot] = t
                    logits, srv.cache = srv._step(
                        srv.params, srv.cache, jnp.asarray(tok),
                        jnp.asarray(srv.pos))
                    srv.pos[slot] += 1
                srv.cur_tok[slot] = int(jnp.argmax(logits[slot]))
            srv._feed_prompt = _loop_feed
        done = srv.run([Request(i, p) for i, p in enumerate(prompts)])
        return {r.rid: r.generated for r in done}

    fast = run(feed_loop=False)
    ref = run(feed_loop=True)
    assert fast == ref                               # bit-identical greedy
    assert all(len(v) == 5 for v in fast.values())


def test_slot_server_rejects_empty_prompt():
    from repro.configs import get_config, reduced
    from repro.launch.serve import Request, SlotServer
    from repro.models import build_model
    model = build_model(reduced(get_config("llama3.2-3b")))
    srv = SlotServer(model, slots=1, max_seq=16, eos=None, max_gen=2)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit(Request(0, np.zeros((0,), np.int64)))


# ---------------------------------------------------------------------------
# cache manifest / prewarm (restart warm-up from a spec registry)
# ---------------------------------------------------------------------------

def test_projector_spec_dict_roundtrip():
    spec = rp.ProjectorSpec(family="cp", k=64, dims=(4, 8), rank=3,
                            dtype=jnp.bfloat16, backend="xla")
    back = rp.ProjectorSpec.from_dict(spec.to_dict())
    assert back == spec and hash(back) == hash(spec)  # cache-key identical
    import json
    json.dumps(spec.to_dict())                        # JSON-able as claimed
    with pytest.raises(ValueError, match="dtype"):
        rp.ProjectorSpec.from_dict({**spec.to_dict(), "dtype": "no_such"})


def test_cache_manifest_prewarm_bitwise_and_stats():
    a = rp.ProjectorSpec(family="tt", k=64, dims=(4, 8, 8), rank=2)
    b = rp.ProjectorSpec(family="cp", k=32, dims=(8, 8), rank=2)
    cache = OperatorCache(capacity=4)
    cache.get(a, seed=3)
    cache.get(b, seed=9)
    man = cache.manifest()
    assert [e["seed"] for e in man] == [3, 9]         # LRU-first order

    warm = OperatorCache(capacity=4)
    assert warm.prewarm(man) == 2
    st = warm.stats
    assert st.prewarmed == 2 and st.misses == 0 and st.hits == 0
    assert "prewarmed" in st.as_dict()
    x = np.arange(4 * 8 * 8, dtype=np.float32)
    y_orig = np.asarray(rp.project(cache.get(a, seed=3), x))
    y_warm = np.asarray(rp.project(warm.get(a, seed=3), x))
    np.testing.assert_array_equal(y_orig, y_warm)     # bitwise regeneration
    assert warm.stats.hits == 1 and warm.stats.misses == 0
    # idempotent: prewarming again samples nothing, only refreshes recency
    assert warm.prewarm(man) == 0 and warm.stats.prewarmed == 2
    # capacity still enforced during prewarm
    tiny = OperatorCache(capacity=1)
    assert tiny.prewarm(man) == 2
    assert tiny.stats.evictions == 1 and len(tiny) == 1


def test_server_save_manifest_prewarm_file(tmp_path):
    srv = SketchServer(ServeConfig())
    x = np.zeros((4 * 8 * 8,), np.float32)
    srv.submit(x, SPEC, seed=1, now=0.0)
    srv.tick(1.0, force=True)
    path = tmp_path / "ops.json"
    assert srv.save_manifest(path) == 1
    assert b"cores" not in path.read_bytes()          # specs only, no weights

    srv2 = SketchServer(ServeConfig())
    assert srv2.prewarm(path) == 1
    srv2.submit(x, SPEC, seed=1, now=0.0)
    srv2.tick(1.0, force=True)
    assert srv2.cache.stats.hits == 1 and srv2.cache.stats.misses == 0
    with pytest.raises(ValueError, match="entries"):
        bad = tmp_path / "bad.json"
        bad.write_text('{"version": 1}')
        srv2.prewarm(bad)
