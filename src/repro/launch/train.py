"""Training launcher.

Examples
--------
# CPU-runnable reduced config, 200 steps with checkpoints:
PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --reduced \
    --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ck

# Compressed cross-pod gradient sync (needs a pod axis => >= 2x2x2 devices):
PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --reduced \
    --mesh 2x2x2 --compress tt:k=1024,rank=8,dims=4x8x16 --steps 50

On a real TPU pod the same flags apply with --mesh 16x16 / 2x16x16 and the
full (non---reduced) configs.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, reduced
from repro.data import DataConfig, SyntheticLM
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import build_model
from repro.models.config import ShapeSpec
from repro.optim import schedule
from repro.optim.compress import SketchCompressor, parse_compress_flag
from repro.runtime import train_loop
from repro.runtime.resilience import FaultInjector


def parse_mesh(spec: str | None):
    if spec is None:
        return make_host_mesh()
    dims = tuple(int(x) for x in spec.split("x"))
    names = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, names, devices=jax.devices()[: _prod(dims)])


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized smoke variant of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default=None, help="e.g. 2x2x2 / 16x16")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress", default=None,
                    help="tt:k=...,rank=...[,dims=AxBxC][,order=N]")
    ap.add_argument("--compress-sync", default="local-mean",
                    choices=["local-mean", "sketch-mean"],
                    help="cross-pod sync of compress_collective: pmean the "
                         "dense reconstructions (one adjoint pass) or the "
                         "(buckets, k) sketches (k-sized wire bytes)")
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sketch-ef-ckpt", action="store_true",
                    help="checkpoint the error-feedback tree as a (seed, "
                         "spec, sketch) record instead of its dense bytes "
                         "(requires --compress; the operator is regenerated "
                         "from the saved seed on restore)")
    ap.add_argument("--crash-at", type=int, default=None,
                    help="fault injection (tests): raise at this step once")
    ap.add_argument("--monitor", action="store_true",
                    help="O(k) sketch telemetry: param norm/drift per log")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)
    npod = mesh.shape.get("pod", 1) if hasattr(mesh.shape, "get") else (
        mesh.shape["pod"] if "pod" in mesh.axis_names else 1)
    shape = ShapeSpec("cli_train", args.seq, args.batch, "train")

    compressor = None
    if args.compress:
        compressor = SketchCompressor(parse_compress_flag(args.compress),
                                      sync=args.compress_sync)
        print(f"[compress] {args.compress} sync={args.compress_sync} "
              f"shrinkage={compressor.cfg.shrinkage():.4f}")

    lr_fn = functools.partial(schedule.cosine_with_warmup, peak_lr=args.lr,
                              warmup_steps=args.warmup,
                              total_steps=args.steps)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    with mesh:
        bundle = steps_lib.build_train_step(
            model, mesh, shape, lr_fn=lr_fn, remat=args.remat,
            compressor=compressor)
        state = steps_lib.init_train_state(
            model, jax.random.PRNGKey(args.seed), compressor=compressor,
            npod=npod if compressor is not None else 1)
        injector = (FaultInjector({args.crash_at})
                    if args.crash_at is not None else None)
        on_metrics = None
        if args.monitor:
            from repro.core import PytreeSketcher, SketchConfig, SketchMonitor
            mon_cfg = SketchConfig(family="tt", k=256, rank=2,
                                   bucket_elems=4 * 8 * 16, dims=(4, 8, 16),
                                   fresh_per_step=False)
            monitor = SketchMonitor(
                PytreeSketcher(mon_cfg, state["params"]),
                jax.random.PRNGKey(17))

            def on_metrics(step, metrics, live_state):
                if step % 10 == 0:
                    m = monitor.update(live_state["params"])
                    print(f"   [monitor] step {step} "
                          f"sketch_norm={float(m['sketch_norm']):.4f} "
                          f"drift={float(m['sketch_drift']):.5f}")
        ef_codec = None
        if args.sketch_ef_ckpt:
            if compressor is None or "ef" not in state:
                raise ValueError(
                    "--sketch-ef-ckpt needs error-feedback state: pass "
                    "--compress so the train state carries an 'ef' tree")
            from repro.ckpt import SketchedTreeCodec
            from repro.launch import sharding as sh
            ef_codec = SketchedTreeCodec(
                compressor.cfg, jax.eval_shape(lambda: state["ef"]),
                mesh=mesh, bucket_spec=sh.bucket_specs(mesh))
            print(f"[ckpt] sketched EF records: "
                  f"{ef_codec.dense_bytes()} -> {ef_codec.sketch_bytes()} "
                  f"bytes ({ef_codec.compression_ratio():.1f}x)")
        loop_cfg = train_loop.LoopConfig(
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, npod=npod)
        state, final = train_loop.run(bundle.fn, state, data, loop_cfg,
                                      injector=injector,
                                      on_metrics=on_metrics,
                                      ef_codec=ef_codec)
    print(f"[train] finished at step {final} "
          f"(params={sum(x.size for x in jax.tree.leaves(state['params']))})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
