"""Serving-engine benchmarks: trace replay, operator cache, retrieval sweep.

Three row families, all seeded and deterministic in structure:

  serve/trace/mixed — a mixed dense+TT+CP trace through the dynamic
      batcher under `rp.force_pallas()` + `rp.dispatch_stats()`; derived
      carries the GATED `launches_project` (one kernel dispatch per batcher
      tick — the engine's core claim), plus occupancy and latency
      percentiles of the flush policy.
  serve/cache       — operator-cache hit vs regeneration cost on a
      repeated-spec trace (hit rate is an acceptance criterion: >= 0.9).
  serve/query       — the brute-force-but-batched top-m similarity sweep
      over a large sketch store (one device program per query batch).
"""
import time

import numpy as np

from repro import rp
from repro.serve import ServeConfig, SketchServer, SketchStore, replay, \
    synth_trace

from ._util import csv_row, time_call

SPEC = rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2)


def _trace_row(rows, n_requests):
    cfg = ServeConfig(max_batch=8, flush_us=1_000.0)
    server = SketchServer(cfg, SketchStore(SPEC))
    trace = synth_trace(n_requests, [(SPEC, 0)], seed=3)
    with rp.dispatch_stats() as st, rp.force_pallas():
        rep = replay(server, trace)
    assert rep["requests_done"] == n_requests, rep
    assert st.kernel_calls == rep["ticks"], (st.kernel_calls, rep["ticks"])
    us = rep["wall_s"] * 1e6 / n_requests
    rows.append(csv_row(
        f"serve/trace/mixed/B={n_requests}", us,
        f"launches_project={st.kernel_calls};ticks={rep['ticks']};"
        f"requests={rep['requests_done']};"
        f"occupancy={rep['occupancy_mean']:.3f};"
        f"p50_us={rep['p50_us']:.0f};p99_us={rep['p99_us']:.0f};"
        f"hit_rate={rep['cache']['hit_rate']:.3f}"))


def _cache_row(rows, n_requests):
    # dense-only repeated-spec trace: every tick after the first is a cache
    # hit, so hit_rate -> 1 as the trace grows (acceptance: >= 0.9)
    cfg = ServeConfig(max_batch=4, flush_us=500.0)
    server = SketchServer(cfg)
    trace = synth_trace(n_requests, [(SPEC, 0)], mix=(1.0, 0.0, 0.0), seed=5)
    rep = replay(server, trace)
    c = rep["cache"]
    t0 = time.perf_counter()
    server.cache.get(SPEC, 0)                       # a pure LRU hit
    hit_us = (time.perf_counter() - t0) * 1e6
    regen_us = c["regen_s"] * 1e6 / max(c["misses"], 1)
    rows.append(csv_row(
        "serve/cache", hit_us,
        f"hits={c['hits']};misses={c['misses']};"
        f"hit_rate={c['hit_rate']:.3f};evictions={c['evictions']};"
        f"regen_us_per_miss={regen_us:.0f}"))
    assert c["hit_rate"] >= 0.9, c


def _query_row(rows, n_store, tile):
    store = SketchStore(SPEC, query_tile=tile)
    rng = np.random.default_rng(0)
    # ingest in slabs (the growable array doubles underneath)
    for start in range(0, n_store, 16384):
        b = min(16384, n_store - start)
        store.add(rng.standard_normal((b, SPEC.k)).astype(np.float32))
    q = rng.standard_normal((8, SPEC.k)).astype(np.float32)
    us = time_call(lambda: store.query(q, top_m=10), warmup=1, repeat=3)
    rows.append(csv_row(
        f"serve/query/n={n_store}", us,
        f"top_m=10;tile={tile};batch=8;"
        f"eps={store.eps_bound():.2f};mib={store.nbytes() / 2**20:.1f}"))


def run(fast=True):
    rows = []
    _trace_row(rows, 64)
    _cache_row(rows, 96 if fast else 512)
    _query_row(rows, 65_536 if fast else 1_048_576, 8_192)
    return rows
