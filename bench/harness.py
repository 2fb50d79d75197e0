"""Runs one cell once: set-up, a measured window, the comparison with the
plain reference, and one result line.

Everything a cell needs is found by name: the workload in BENCHMARK.json
names its configuration (`bench/configs/<config>.json`) and its traffic
(`bench/traffic/<traffic>.json`, read by `bench/arrivals.py`), whose
`system` names the adapter `bench/systems/<system>.py` and whose arrival
process is `bench/processes/<process>.py`; each metric, end-to-end or
per-layer, is read by `bench/metrics/<metric>.py`.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import arrivals, tracered
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
WINDOW = "bench.window"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding a cell's parts ------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{wl['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return {"workload": wl, "config": cfg, "traffic": mix,
            "limits": cfg["limits"],
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def system(mix: dict):
    return importlib.import_module(f"bench.systems.{mix['system']}")


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- host spans -----------------------------------------------------------------

class Spans:
    """Wall-clock spans the harness puts around its calls into the program;
    in a traced run each also enters a profiler annotation, so the trace
    can name what the host was doing in a device-idle gap."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.durations: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)


class CompileCount:
    """XLA compile requests (cache hits included) seen since `reset`."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == self.EVENT:
            self.n += 1


# -- the window ------------------------------------------------------------------

def closed_loop(sut, seconds: float, spans: Spans) -> dict:
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        with spans(sut.span):
            sut.step()
        n += 1
    t1 = time.perf_counter()
    return {"attempted": n, "failed": 0, "window_s": t1 - t0,
            "work": sut.work_per_step() * n}


def open_loop(sut, reqs, spans: Spans) -> dict:
    """Offers `reqs` at their due times, whatever the system does; each
    latency runs from due time to completion, so a stall delays everyone
    behind it."""
    n = len(reqs)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    failed = 0
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and reqs[i].due <= now:
            late[i] = now - reqs[i].due
            sut.submit(reqs[i], now)
            i += 1
        if i == n and not sut.pending():
            break
        if sut.ready(now):
            served, bad = sut.serve(now, spans)
            done[served] = time.perf_counter() - t0
            done[bad] = np.inf
            failed += len(bad)
            continue
        wakes = [reqs[i].due] if i < n else []
        nxt = sut.next_wake()
        if nxt is not None:
            wakes.append(nxt)
        pause = min(wakes) - (time.perf_counter() - t0) if wakes else 0.0
        if pause > 0:
            time.sleep(min(pause, 0.002))
    t1 = time.perf_counter()
    due = np.array([r.due for r in reqs])
    ok = np.isfinite(done)
    lat = done[ok] - due[ok]
    log(f"generator lateness s: p50 {np.percentile(late, 50):.6f} "
        f"p95 {np.percentile(late, 95):.6f} max {late.max():.6f} "
        f"over {n} arrivals")
    return {"attempted": n, "failed": failed + int(np.isnan(done).sum()),
            "window_s": t1 - t0, "latency_s": lat,
            "work": sut.work_of([r.idx for r, k in zip(reqs, ok) if k])}


# -- one run ---------------------------------------------------------------------

def trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run(args, t_process: float) -> int:
    import jax

    devices = jax.devices()
    cell = load_cell(args.workload)
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu":
        log(f"bench: JAX found no TPU (first device: {devices[0].platform});"
            " refusing to run")
        return 2
    if len(devices) < chips:
        log(f"bench: {args.workload} needs {chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    enable_cache()
    measure(cell, args.seed, args.seconds, bool(args.trace), t_process,
            devices[:chips])
    return 0


def enable_cache() -> str:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR when set,
    else one fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(cell, seed, seconds, traced, t_process, devices, *,
            build=None, chip_peaks=peaks, control=None) -> dict:
    """Set up the cell, measure one window, compare, print the result line
    and return it.

    `build(cfg, mix, seed)` replaces the system's own constructor and
    `chip_peaks(device_kind)` the peak table: the tests plant faults and
    run on the CPU through them. `control` (a precision below the one the
    configuration states) puts the plain reference at that precision in
    the program's place for the comparison (`bench/control.py`)."""
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    mod = system(mix)
    compiles = CompileCount()
    sut = (build or mod.build)(cfg, mix, seed)
    reqs = arrivals.requests(mix, seconds, seed,
                             n_items=getattr(sut, "n_items", None))
    if reqs is not None:
        sut.prepare(reqs)
    spans = Spans(traced)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    counters0 = sut.counters()
    # The harness's own pre-made requests and payloads, and everything
    # set-up built, leave the collector's scans: a pause in the window then
    # comes from what the window allocates.
    gc.collect()
    gc.freeze()
    compiles.n = 0
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    with contextlib.ExitStack() as es:
        if traced:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_options())
            es.callback(jax.profiler.stop_trace)
        with jax.profiler.TraceAnnotation(WINDOW):
            if mix["loop"] == "closed":
                out = closed_loop(sut, seconds, spans)
            else:
                out = open_loop(sut, reqs, spans)
    gc.unfreeze()
    log(f"compiles in the window: {compiles.n}")
    counters = {k: (v, sut.counters()[k]) for k, v in counters0.items()}
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    ctx = {"window": out, "setup_s": setup_s, "notes": []}
    metrics = cell["end_to_end"]
    if traced:
        ctx.update(trace_context(trace_dir, out, spans, counters,
                                 chip_peaks(dev.device_kind)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx["busy_s"]
        device["window_s"] = ctx["window_s"]
        result["breakdown"] = {"device_ops": tracered.top_ops(ctx["ops"]),
                               "idle_gaps": ctx["idle_gaps"]}
        metrics = cell["per_layer"]
    for m in metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    for note in ctx["notes"]:
        log(note)
    sut.close()
    checks = sut.check(control)
    limits = cell["limits"]
    missing = set(checks) - set(limits)
    if missing or not checks:
        raise ValueError(f"numbers compared without a limit: {missing}")
    result["correct"] = bool(
        all(checks[k] <= limits[k] for k in checks)
        and out["failed"] == 0 and out["attempted"] > 0)
    if control is not None:
        # the program's own readings on the same seed, beside the control's
        result["program_checks"] = sut.check(None)
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in checks}
    print(json.dumps(result), flush=True)
    for k in checks:
        log(f"check {k}: {checks[k]!r} (limit {limits[k]!r})")
    return result


def trace_context(trace_dir, out, spans, counters, chip) -> dict:
    profile = tracered.load(trace_dir)
    t0, t1 = tracered.annotation(profile, WINDOW)
    ops = tracered.clip(tracered.device_ops(profile), t0, t1)
    host = tracered.host_spans(profile, "bench.")
    kernels = json.loads((HERE / "kernels.json").read_text())
    return {"ops": ops, "window_s": t1 - t0,
            "busy_s": tracered.busy_seconds(ops),
            "idle_gaps": tracered.idle_gaps(
                ops, t0, t1, [s for s in host if s[0] != WINDOW]),
            "spans": spans.durations, "counters": counters,
            "work": out["work"], "peaks": chip, "kernels": kernels}


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run(args, t_process)
