"""Aggregates the dry-run sweep JSONs into the roofline table used by
EXPERIMENTS.md (§Dry-run / §Roofline), plus the plan-driven per-kernel
rooflines (roofline/kernel/*): analytic TPU-time bounds for the batched
sweep and carry-sweep launches whose flops AND HBM bytes are read from the
`ExecutionPlan` cost ledger (`rp.plan_execution(...).cost`) — the SAME
resolver every dispatch and every timing row goes through, so the tables
can never disagree on traffic. Each
kernel row carries both schedules' bounds — `serial_s` (compute + memory,
back-to-back phases) and `pipelined_s` (max(compute, memory): the
double-buffered DMA schedule overlaps the streams) — and the
`pipeline_gain` their ratio predicts on hardware."""
import json
import pathlib

from ._util import csv_row


def _kernel_rows(rows):
    from repro import rp
    from repro.launch.roofline import TARGET_DEVICE_KIND, chip_peaks
    peaks = chip_peaks(TARGET_DEVICE_KIND)

    def bound(name, cost, extra=""):
        compute_s = cost.flops / peaks.flops
        memory_s = cost.hbm_bytes / peaks.hbm_bw
        serial_s = compute_s + memory_s
        pipelined_s = max(compute_s, memory_s)
        rows.append(csv_row(
            f"roofline/kernel/{name}", 0.0,
            f"flops={cost.flops};hbm_bytes={cost.hbm_bytes};"
            f"compute_s={compute_s:.3e};memory_s={memory_s:.3e};"
            f"serial_s={serial_s:.3e};pipelined_s={pipelined_s:.3e};"
            f"pipeline_gain={serial_s / pipelined_s:.3f};"
            f"bottleneck={'compute' if compute_s > memory_s else 'memory'}"
            f"{extra}"))

    k, rank, b = 128, 2, 8
    dims = (256, 16, 16)             # the perf/pipeline/sweep bench shape
    for family in ("tt", "cp"):
        ep = rp.plan_execution(
            rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
            rp.StructureSig(batch=b), backend="pallas", pipeline="double")
        bound(f"sweep/{family}", ep.cost,
              f";dims={'x'.join(map(str, dims))};B={b}")
    bc, r_in, cdims = 64, 4, (16, 16, 16)
    for family in ("tt", "cp"):
        ep = rp.plan_execution(
            rp.ProjectorSpec(family=family, k=k, dims=cdims, rank=rank),
            rp.StructureSig(structure="tt", batch=bc, in_rank=r_in),
            backend="pallas", pipeline="double")
        bound(f"carry/{family}x tt".replace(" ", ""), ep.cost,
              f";dims={'x'.join(map(str, cdims))};B={bc};r_in={r_in}")


def run(fast=True, out_dir="experiments/dryrun"):
    rows = []
    _kernel_rows(rows)
    p = pathlib.Path(out_dir)
    if not p.exists():
        rows.append(csv_row("roofline/none", 0.0, "run launch/sweep.sh first"))
        return rows
    for f in sorted(p.glob("*.json")):
        cell = json.loads(f.read_text())
        if cell.get("status") == "skip":
            rows.append(csv_row(f"roofline/{f.stem}", 0.0, "SKIP"))
            continue
        r = cell["roofline"]
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        frac = r["compute_s"] / dom if dom else 0.0
        rows.append(csv_row(
            f"roofline/{f.stem}", 0.0,
            f"compute={r['compute_s']:.4f}s;memory={r['memory_s']:.4f}s;"
            f"collective={r['collective_s']:.4f}s;"
            f"bottleneck={r['bottleneck']};roofline_frac={frac:.3f};"
            f"useful={r['useful_flops_frac']:.3f}"))
    return rows
