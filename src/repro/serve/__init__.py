"""repro.serve — the sketch-serving engine (RP-as-a-service).

The JL guarantee (paper Thm 1) means a stored `(n_buckets, k)` sketch
preserves Euclidean distances, so nearest-neighbor and pairwise-similarity
queries are answered ENTIRELY in the compressed domain. This subsystem is
that workload as a serving layer on top of the kernel/dispatch stack:

  queue -> batcher -> one dispatch per tick -> sketch store -> retrieval

  * `DynamicBatcher`  — lane-keyed request queue with a max-batch /
    max-latency flush policy; heterogeneous in-flight requests (dense, TT,
    CP; rank- and length-ragged) coalesce so one tick is one
    `rp.project_many` kernel dispatch.
  * `OperatorCache`   — LRU keyed on (ProjectorSpec, seed); operators are a
    seed plus shapes, so a hit means zero regeneration (hit/miss/regen
    stats included).
  * `SketchStore`     — millions of stored k-vectors held on the device;
    brute-force-but-batched top-m retrieval as one device program per
    query, plus a pairwise endpoint, every answer carrying the Thm-1
    distortion bound.
  * `SketchServer`    — the engine tying the above together (clock-explicit
    and deterministic; an async transport goes on top).
  * `synth_trace` / `replay` — the offline load generator reporting
    p50/p99 latency, batch occupancy, and cache hit-rate.

CLI driver: `python -m repro.launch.serve_rp`; quickstart:
`examples/serve_sketch.py`.
"""
from .batcher import DynamicBatcher, LaneKey, SketchRequest, structure_tag
from .cache import CacheStats, OperatorCache
from .config import ServeConfig
from .engine import SketchServer
from .loadgen import TraceEvent, replay, synth_trace
from .store import PairwiseResult, QueryResult, SketchStore

__all__ = [
    "CacheStats", "DynamicBatcher", "LaneKey", "OperatorCache",
    "PairwiseResult", "QueryResult", "ServeConfig", "SketchRequest",
    "SketchServer", "SketchStore", "TraceEvent", "replay", "structure_tag",
    "synth_trace",
]
