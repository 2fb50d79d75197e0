"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell against
ShapeDtypeStruct inputs (no allocation), print memory/cost analysis, and emit
the three-term roofline JSON consumed by EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any OTHER import (jax locks the device
# count on first initialization). Only the module docstring precedes them.

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax

from repro.configs import ARCHS, get_config
from repro.launch import roofline as rl
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, settings


def build_bundle(cfg, shape, mesh, *, remat: str = "nothing",
                 seq_parallel: bool = True, compressor=None):
    model = build_model(cfg)
    if shape.kind == "train":
        return steps.build_train_step(model, mesh, shape, remat=remat,
                                      seq_parallel=seq_parallel,
                                      compressor=compressor)
    if shape.kind == "prefill":
        return steps.build_prefill_step(model, mesh, shape, remat=remat,
                                        seq_parallel=seq_parallel)
    return steps.build_serve_step(model, mesh, shape)


def probe_pair(cfg):
    """(cfg_n1, cfg_n2, n1, n2, n_full): small-depth unrolled cost probes.

    Costs are linear in depth "instances" (one instance = one repetition of
    the arch's layer pattern): two probes pin slope+intercept, the full cell
    extrapolates. lax.scan bodies are otherwise counted ONCE by XLA's cost
    analysis, which under-reports flops/collectives by ~L.
    """
    if cfg.family == "hybrid":
        p = 3
    elif cfg.family == "encdec":
        p = 1
    else:
        p = len(cfg.window_pattern)
    kw1 = {"n_layers": p}
    kw2 = {"n_layers": 2 * p}
    if cfg.family == "encdec":
        kw1["encoder_layers"] = 1
        kw2["encoder_layers"] = 2
    n_full = cfg.n_layers / p
    return (dataclasses.replace(cfg, **kw1), dataclasses.replace(cfg, **kw2),
            1.0, 2.0, n_full)


def _measure(cfg, shape, mesh, *, remat, seq_parallel, compressor):
    bundle = build_bundle(cfg, shape, mesh, remat=remat,
                          seq_parallel=seq_parallel, compressor=compressor)
    t0 = time.time()
    compiled = bundle.fn.lower(*bundle.args).compile()
    return bundle, compiled, time.time() - t0


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str | None, remat: str = "nothing",
             seq_parallel: bool = True, verbose: bool = True,
             tag: str = "", compress: str | None = None,
             compress_sync: str = "local-mean",
             cfg_override=None, opts: dict | None = None) -> dict:
    cfg = cfg_override or get_config(arch)
    shape = cfg.shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "kind": shape.kind}
    if shape.skip:
        cell["status"] = "skip"
        cell["reason"] = shape.skip
        return _emit(cell, out_dir, verbose, tag)

    mesh = make_production_mesh(multi_pod=multi_pod)
    compressor = None
    if compress:
        from repro.optim.compress import SketchCompressor, parse_compress_flag
        compressor = SketchCompressor(parse_compress_flag(compress),
                                      pod_axis="pod" if multi_pod else None,
                                      sync=compress_sync)
    n_dev = mesh.devices.size
    opts = opts or {}
    with mesh, settings.override(**opts):
        # 1) full-depth rolled compile: proves sharding coherence + memory fit
        bundle, compiled, t_full = _measure(
            cfg, shape, mesh, remat=remat, seq_parallel=seq_parallel,
            compressor=compressor)
        mem = rl.memory_stats(compiled)
        # 2) two shallow UNROLLED probes: exact per-instance costs
        c1, c2, n1, n2, n_full = probe_pair(cfg)
        probe_chunk = max(2048, min(4096, shape.seq_len))
        with settings.override(unroll_scans=True, attn_chunk_q=probe_chunk,
                               attn_chunk_k=probe_chunk):
            _, comp1, t1 = _measure(c1, shape, mesh, remat=remat,
                                    seq_parallel=seq_parallel,
                                    compressor=compressor)
            _, comp2, t2 = _measure(c2, shape, mesh, remat=remat,
                                    seq_parallel=seq_parallel,
                                    compressor=compressor)
    roof = rl.analyze_extrapolated(
        comp1, comp2, n1, n2, n_full, arch=arch, shape=shape,
        mesh_name=mesh_name, n_devices=n_dev, cfg=cfg, memory=mem)
    cell.update(status="ok", compile_s=round(t_full, 1),
                probe_compile_s=[round(t1, 1), round(t2, 1)],
                notes=bundle.notes, roofline=roof.to_json())
    if verbose:
        print(compiled.memory_analysis())
    return _emit(cell, out_dir, verbose, tag)


def _emit(cell: dict, out_dir: str | None, verbose: bool, tag: str) -> dict:
    if out_dir:
        p = pathlib.Path(out_dir)
        p.mkdir(parents=True, exist_ok=True)
        name = f"{cell['arch']}_{cell['shape']}_{cell['mesh']}{tag}.json"
        (p / name).write_text(json.dumps(cell, indent=1))
    if verbose:
        if cell["status"] == "skip":
            print(f"SKIP {cell['arch']} {cell['shape']}: {cell['reason']}")
        else:
            r = cell["roofline"]
            print(f"OK {cell['arch']} {cell['shape']} {cell['mesh']}: "
                  f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                  f"collective={r['collective_s']:.4f}s "
                  f"bottleneck={r['bottleneck']} "
                  f"useful={r['useful_flops_frac']:.2f} "
                  f"(compile {cell['compile_s']:.0f}s)")
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--compress", default=None,
                    help="e.g. tt:k=4096,rank=2 — sketched grad all-reduce")
    ap.add_argument("--compress-sync", default="local-mean",
                    choices=["local-mean", "sketch-mean"],
                    help="compress_collective sync mode on the pod axis")
    ap.add_argument("--cast-once", action="store_true",
                    help="perf: bf16 param cast before the scan")
    ap.add_argument("--flash-bf16", action="store_true",
                    help="perf: bf16 softmax weights in flash PV matmul")
    ap.add_argument("--sp-outputs", action="store_true",
                    help="perf: seq-shard block outputs (reduce-scatter)")
    ap.add_argument("--moe-c-shard", action="store_true",
                    help="perf: capacity-shard expert buffer when E < |model|")
    ap.add_argument("--no-head-constraints", action="store_true",
                    help="perf: let the partitioner pick attention shardings")
    ap.add_argument("--no-gqa-expand", action="store_true",
                    help="perf: keep grouped (Hkv, G) flash layout")
    ap.add_argument("--tag", default="", help="suffix for output json")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.list:
        for name, cfg in ARCHS.items():
            for s in cfg.shapes:
                flag = f"SKIP({s.skip[:30]}...)" if s.skip else "run"
                print(f"{name:20s} {s.name:12s} {flag}")
        return 0

    assert args.arch and args.shape, "--arch and --shape required (or --list)"
    opts = {}
    if args.cast_once:
        opts["cast_params_once"] = True
    if args.flash_bf16:
        opts["flash_p_bf16"] = True
    if args.sp_outputs:
        opts["sp_block_outputs"] = True
    if args.moe_c_shard:
        opts["moe_c_shard"] = True
    if args.no_head_constraints:
        opts["constrain_attn_heads"] = False
    if args.no_gqa_expand:
        opts["gqa_expand"] = False
    cell = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                    out_dir=args.out, remat=args.remat,
                    seq_parallel=not args.no_seq_parallel, tag=args.tag,
                    compress=args.compress,
                    compress_sync=args.compress_sync, opts=opts)
    return 0 if cell["status"] in ("ok", "skip") else 1


if __name__ == "__main__":
    sys.exit(main())
