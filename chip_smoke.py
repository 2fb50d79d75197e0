#!/usr/bin/env python3
"""Smoke run of the sketching engine's main path on a TPU, at deployment size.

Usage (from the root of a checkout; no installation step):

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the pod-axis sync and the
                                      # sharded projection, nothing else

It refuses to run (exit 2, no result) unless JAX's first device is a TPU:
it never falls back to the CPU. Everything runs in this one process. Each
phase checks its outputs against a float32 reference run at "highest"
matmul precision and asserts that the Pallas kernel route really ran, not
in interpret mode; any failure exits non-zero. The last line of standard
output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):
  (a) dense mode sweeps — `rp.project` / `rp.reconstruct`, backend='auto',
      TT and CP, k=1024, rank 2, over B=96 buckets of 2^20 elements (about
      one llama3.2-3b decoder layer of gradients) at orders 3, 4 and 5;
  (b) structured inputs — the four carry-sweep pairings at dims
      (128, 128, 64), input rank 8, B=1024 items per call;
  (c) serving — a SketchStore of 1,000,000 k=256 sketches projected on the
      chip, a few hundred mixed dense/TT/CP requests through SketchServer
      with near-duplicates of 8 stored items, and 8 top-10 queries;
  (d) the sketched gradient step on one llama3.2-3b decoder layer at its
      published widths: SketchCompressor.compress and the fused
      adamw.update_sketched against the unfused chain.

JAX's persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or to <checkout>/.jax_cache (`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-3          # relative Frobenius error against the reference


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Problem sizes; the defaults are the deployment sizes run on chip."""

    k: int = 1024
    rank: int = 2
    buckets: int = 96
    orders: tuple = ((128, 128, 64), (32, 32, 32, 32), (16, 16, 16, 16, 16))
    struct_dims: tuple = (128, 128, 64)
    struct_rank: int = 8
    struct_batch: int = 1024
    serve_k: int = 256
    serve_dims: tuple = (32, 32, 32)
    store_items: int = 1_000_000
    store_batch: int = 8000
    item_rank: int = 4
    requests: int = 288
    twins: int = 8
    top_m: int = 10
    arch: str = "llama3.2-3b"
    layer_scale: int = 1          # divides the layer's widths (tests only)
    sketch_dims: tuple = (128, 128, 64)
    ref_bytes: int = 1 << 30      # per-call intermediate cap of references


@dataclasses.dataclass
class PhaseResult:
    name: str
    compile_s: float
    run_s: float
    kernel_calls: int
    interpret_calls: int
    max_err: float

    def line(self, device: str) -> str:
        return (f"phase {self.name}: device={device} "
                f"compile_s={self.compile_s:.3f} run_s={self.run_s:.3f} "
                f"kernel_dispatches={self.kernel_calls} "
                f"interpret_dispatches={self.interpret_calls} "
                f"max_rel_err={self.max_err:.3e}")


def _rel_err(a, b) -> float:
    import jax
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jax.device_put(jnp.asarray(b, jnp.float32), a.sharding)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


def _tree_err(a, b) -> float:
    import jax
    return max(_rel_err(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _stats():
    """A fresh dispatch-stats scope that keeps an enclosing
    `rp.force_pallas()` (tests force the kernels off-TPU)."""
    import contextlib
    from repro import rp
    forced = rp.current_stats().force_pallas
    stack = contextlib.ExitStack()
    st = stack.enter_context(rp.dispatch_stats())
    if forced:
        stack.enter_context(rp.force_pallas())
    return stack, st


def _run(name, fn, *, kernels, interpret_ok):
    """Run `fn` cold (compile + run) inside a dispatch-stats scope — the
    dispatch happens when code is traced, so a jitted `fn` dispatches on
    its first call only — then warm. Checks the cold run dispatched
    exactly `kernels` Pallas kernels (when None: at least one), none of
    them interpreted unless `interpret_ok`. Returns (warm output, partial
    PhaseResult)."""
    import jax
    t0 = time.perf_counter()
    scope, st = _stats()
    with scope:
        jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    if kernels is None:
        assert st.kernel_calls > 0, f"{name}: no Pallas kernel dispatched"
    else:
        assert st.kernel_calls == kernels, (
            f"{name}: {st.kernel_calls} Pallas dispatches, expected "
            f"{kernels}; breakdown {st.breakdown}")
    assert interpret_ok or st.interpret_calls == 0, (
        f"{name}: {st.interpret_calls} kernel dispatches in interpret mode")
    res = PhaseResult(name, max(0.0, (t1 - t0) - (t2 - t1)), t2 - t1,
                      st.kernel_calls, st.interpret_calls, 0.0)
    return out, res


def _check(res: PhaseResult, err: float) -> PhaseResult:
    res.max_err = max(res.max_err, err)
    assert err <= TOL, f"{res.name}: relative error {err:.3e} > {TOL}"
    return res


def _highest():
    import jax
    return jax.default_matmul_precision("highest")


def _ref_batched(fn, x, n):
    """`fn` over `x` in leading-axis chunks of `n` (bounds the einsum
    references' intermediates), concatenated."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(fn)
    with _highest():
        return jnp.concatenate([f(x[i:i + n]) for i in range(0, len(x), n)])


# ---------------------------------------------------------------------------
# (a) dense mode sweeps
# ---------------------------------------------------------------------------

def phase_dense(sz: Sizes, key, *, interpret_ok: bool = False) -> list:
    import jax
    import jax.numpy as jnp
    from repro import rp
    from repro.core.formats import _prod
    out = []
    for n, dims in enumerate(sz.orders):
        x = jax.random.normal(jax.random.fold_in(key, n),
                              (sz.buckets,) + dims, jnp.float32)
        per_item = 4 * _prod(dims[:-1]) * sz.k * sz.rank
        chunk = max(1, sz.ref_bytes // per_item)
        for family in ("tt", "cp"):
            op = rp.make_projector(
                rp.ProjectorSpec(family=family, k=sz.k, dims=dims,
                                 rank=sz.rank),
                jax.random.fold_in(key, 100 + n))

            def both(op=op, x=x):
                y = rp.project(op, x, backend="auto")
                return y, rp.reconstruct(op, y, backend="auto")

            name = f"a/{family}/{'x'.join(map(str, dims))}"
            (y, xh), res = _run(name, both, kernels=2,
                                interpret_ok=interpret_ok)
            y_ref = _ref_batched(
                lambda xc, op=op: rp.project(op, xc, backend="xla"), x, chunk)
            xh_ref = _ref_batched(
                lambda yc, op=op: rp.reconstruct(op, yc, backend="xla",
                                                 chunk=128), y, 8)
            _check(res, _rel_err(y, y_ref))
            out.append(_check(res, _rel_err(xh, xh_ref)))
            del y, xh, y_ref, xh_ref
        del x
    return out


# ---------------------------------------------------------------------------
# (b) structured inputs: the four carry-sweep pairings
# ---------------------------------------------------------------------------

def _batched_input(family, key, b, dims, rank):
    """A seeded batch of b rank-`rank` TT or CP tensors, unit-scale."""
    import jax
    from repro.core.formats import BatchedCPTensor, BatchedTTTensor
    keys = jax.random.split(key, len(dims))
    if family == "cp":
        return BatchedCPTensor(tuple(
            jax.random.normal(kk, (b, d, rank)) / (d * rank) ** 0.5
            for kk, d in zip(keys, dims)))
    ranks = (1,) + (rank,) * (len(dims) - 1) + (1,)
    return BatchedTTTensor(tuple(
        jax.random.normal(kk, (b, ranks[n], d, ranks[n + 1]))
        / (d * ranks[n]) ** 0.5
        for n, (kk, d) in enumerate(zip(keys, dims))))


def _slice_fields(xb, i, j):
    import jax
    return jax.tree.map(lambda a: a[i:j], xb)


def phase_struct(sz: Sizes, key, *, interpret_ok: bool = False) -> list:
    import jax
    import jax.numpy as jnp
    from repro import rp
    out = []
    dims = sz.struct_dims
    for n, (op_f, in_f) in enumerate([("tt", "tt"), ("tt", "cp"),
                                      ("cp", "tt"), ("cp", "cp")]):
        op = rp.make_projector(
            rp.ProjectorSpec(family=op_f, k=sz.k, dims=dims, rank=sz.rank),
            jax.random.fold_in(key, n))
        xb = _batched_input(in_f, jax.random.fold_in(key, 10 + n),
                            sz.struct_batch, dims, sz.struct_rank)
        y, res = _run(f"b/{op_f}x{in_f}/{'x'.join(map(str, dims))}",
                      lambda op=op, xb=xb: rp.project(op, xb, backend="auto"),
                      kernels=1, interpret_ok=interpret_ok)
        f = jax.jit(lambda xc, op=op: rp.project(op, xc, backend="xla"))
        step = 64
        with _highest():
            y_ref = jnp.concatenate([f(_slice_fields(xb, i, i + step))
                                     for i in range(0, sz.struct_batch,
                                                    step)])
        out.append(_check(res, _rel_err(y, y_ref)))
    return out


# ---------------------------------------------------------------------------
# (c) serving
# ---------------------------------------------------------------------------

def phase_serve(sz: Sizes, key, *, interpret_ok: bool = False) -> list:
    import jax
    import numpy as np
    from repro import rp
    from repro.core.formats import random_cp, random_tt
    from repro.serve import ServeConfig, SketchServer, SketchStore

    spec = rp.ProjectorSpec(family="tt", k=sz.serve_k, dims=sz.serve_dims,
                            rank=sz.rank)
    server = SketchServer(ServeConfig(max_batch=32, flush_us=500.0),
                          SketchStore(spec))
    op = server.cache.get(spec, 0)

    def items(i):
        return _batched_input("tt", jax.random.fold_in(key, i),
                              sz.store_batch, spec.dims, sz.item_rank)

    project = jax.jit(lambda xb: rp.project(op, xb, backend="auto"))

    # -- bulk fill: the corpus, projected on the device in batches ----------
    t0 = time.perf_counter()
    scope, st = _stats()
    with scope:                                   # dispatch at trace time
        jax.block_until_ready(project(items(0)))
    t1 = time.perf_counter()
    assert st.kernel_calls == 1, st.breakdown
    assert interpret_ok or st.interpret_calls == 0, st.breakdown
    n_batches = -(-sz.store_items // sz.store_batch)
    for i in range(n_batches):
        ys = np.asarray(project(items(i)))
        server.store.add(ys[:sz.store_items - i * sz.store_batch])
    t2 = time.perf_counter()
    assert len(server.store) == sz.store_items, len(server.store)
    fill = PhaseResult("c/store-fill", max(0.0, (t1 - t0) - (t2 - t1)
                                            / n_batches), t2 - t1,
                       st.kernel_calls, st.interpret_calls, 0.0)
    n0 = min(256, sz.store_batch)
    with _highest():
        ref0 = jax.jit(lambda xb: rp.project(op, xb, backend="xla"))(
            _slice_fields(items(0), 0, n0))
    _check(fill, _rel_err(server.store.get(np.arange(n0)), ref0))

    # -- mixed traffic through the batcher, with planted near-duplicates ----
    twins_of = [int(i) for i in np.linspace(
        7, sz.store_items - 1, sz.twins).astype(np.int64)]
    payloads = []
    for i in range(sz.requests):
        sub = jax.random.fold_in(key, 10_000 + i)
        if i % 3 == 0:
            payloads.append(random_tt(sub, spec.dims, rank=2 + i % 3))
        elif i % 3 == 1:
            payloads.append(random_cp(sub, spec.dims, rank=2 + i % 3))
        else:
            payloads.append(jax.random.normal(sub, spec.dims))
    for j, idx in enumerate(twins_of):
        src = _slice_fields(items(idx // sz.store_batch),
                            idx % sz.store_batch, idx % sz.store_batch + 1)
        dense = np.asarray(src.full()).reshape(spec.dims)
        noise = np.random.default_rng(j).standard_normal(spec.dims)
        rms = float(np.sqrt(np.mean(dense ** 2)))
        payloads.append((dense + 0.01 * rms * noise).astype(np.float32))
    t3 = time.perf_counter()
    scope, st = _stats()
    with scope:
        reqs = [server.submit(p, spec, now=i * 50.0)
                for i, p in enumerate(payloads)]
        server.drain(len(payloads) * 50.0)
    t4 = time.perf_counter()
    dispatches = sum(st.breakdown.values())
    assert dispatches == server.ticks, (
        f"{dispatches} dispatches for {server.ticks} ticks: not one per tick")
    assert st.kernel_calls == server.ticks, st.breakdown
    assert interpret_ok or st.interpret_calls == 0, st.breakdown
    serve = PhaseResult("c/serve-replay", 0.0, t4 - t3, st.kernel_calls,
                        st.interpret_calls, 0.0)
    ref = jax.jit(lambda x: rp.project(op, x, backend="xla"))
    with _highest():
        err = max(_rel_err(r.sketch, ref(p)) for r, p in zip(reqs, payloads))
    _check(serve, err)

    # -- retrieval: each planted twin comes first, beside the stored item
    # -- itself (whose distance 0 and the twin's ~1e-4 relative distance
    # -- may swap under the query matmul's rounding) --------------------
    t5 = time.perf_counter()
    res = server.query(server.store.get(twins_of), sz.top_m)
    t6 = time.perf_counter()
    query = PhaseResult("c/query-top%d" % sz.top_m, 0.0, t6 - t5, 0, 0, 0.0)
    twin_ids = [r.store_id for r in reqs[sz.requests:]]
    for j, idx in enumerate(twins_of):
        ids = [int(i) for i in res.ids[j]]
        assert set(ids[:2]) == {idx, twin_ids[j]}, (j, idx, twin_ids[j], ids)
    return [fill, serve, query]


# ---------------------------------------------------------------------------
# (d) the sketched gradient update on one decoder layer
# ---------------------------------------------------------------------------

def layer_tree(sz: Sizes, key, scale=1.0):
    """Seeded tree with the shapes of one decoder layer of `sz.arch` at its
    published widths (divided by `sz.layer_scale` in tests)."""
    import jax
    from repro.configs import get_config
    cfg = get_config(sz.arch)
    s = sz.layer_scale
    d, ff = cfg.d_model // s, cfg.d_ff // s
    q, kv = cfg.n_heads * cfg.head_dim // s, cfg.n_kv_heads * cfg.head_dim // s
    shapes = {"attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                       "wo": (q, d)},
              "mlp": {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)},
              "norm_attn": (d,), "norm_mlp": (d,)}
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda t: isinstance(t, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        scale * jax.random.normal(kk, shp) for kk, shp in zip(keys, leaves)])


def phase_update(sz: Sizes, key, *, interpret_ok: bool = False) -> list:
    import jax
    import jax.numpy as jnp
    from repro.core.formats import _prod
    from repro.core.sketch import SketchConfig
    from repro.optim import adamw
    from repro.optim.compress import SketchCompressor

    comp = SketchCompressor(SketchConfig(
        k=sz.k, rank=sz.rank, dims=sz.sketch_dims,
        bucket_elems=_prod(sz.sketch_dims)))
    params = layer_tree(sz, jax.random.fold_in(key, 0), 0.02)
    grads = layer_tree(sz, jax.random.fold_in(key, 1), 1e-3)
    ef = {"residual": layer_tree(sz, jax.random.fold_in(key, 2), 1e-4)}
    acfg = adamw.AdamWConfig(clip_norm=None)
    opt = {"m": layer_tree(sz, jax.random.fold_in(key, 3), 1e-4),
           "v": jax.tree.map(jnp.abs,
                             layer_tree(sz, jax.random.fold_in(key, 4), 1e-6)),
           "count": jnp.asarray(4, jnp.int32)}
    lr = jnp.float32(1e-3)
    n_leaves = len(jax.tree.leaves(params))

    unfused, r1 = _run(
        "d/compress+update",
        lambda: (lambda c: adamw.update(params, c[0], opt, lr, acfg)[:2]
                 + (c[1],))(comp.compress(grads, ef, step=opt["count"])),
        kernels=2 * n_leaves, interpret_ok=interpret_ok)
    fused, r2 = _run(
        "d/update_sketched",
        lambda: adamw.update_sketched(params, grads, ef, opt, lr, acfg,
                                      compressor=comp)[:3],
        kernels=2 * n_leaves, interpret_ok=interpret_ok)
    p_u, opt_u, ef_u = unfused
    p_f, opt_f, ef_f = fused
    err = max(_tree_err(p_f, p_u), _tree_err(opt_f["m"], opt_u["m"]),
              _tree_err(opt_f["v"], opt_u["v"]),
              _tree_err(ef_f["residual"], ef_u["residual"]))
    _check(r1, err)
    return [r1, _check(r2, err)]


# ---------------------------------------------------------------------------
# four chips: the pod-axis sync and the sharded projection
# ---------------------------------------------------------------------------

def phase_pod_sync(sz: Sizes, key, mesh, *,
                   interpret_ok: bool = False) -> list:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.formats import _prod
    from repro.core.sketch import SketchConfig
    from repro.optim.compress import SketchCompressor

    npod = mesh.shape["pod"]
    cfg = SketchConfig(k=sz.k, rank=sz.rank, dims=sz.sketch_dims,
                       bucket_elems=_prod(sz.sketch_dims))
    pods = [layer_tree(sz, jax.random.fold_in(key, i), 1e-3)
            for i in range(npod)]
    pod = NamedSharding(mesh, P("pod"))
    one = mesh.devices.flat[0]
    g_pp = jax.device_put(
        jax.tree.map(lambda *xs: jax.numpy.stack(xs), *pods), pod)
    state = {"residual": jax.device_put(
        jax.tree.map(lambda g: 0.1 * g, g_pp), pod)}
    out = []
    for sync in ("sketch-mean", "local-mean"):
        comp = SketchCompressor(cfg, sync=sync, pod_axis="pod")
        coll = jax.jit(lambda g, s, comp=comp: comp.compress_collective(
            g, s, step=0, mesh=mesh)[:2])
        (g_c, s_c), res = _run(f"pod/compress_collective/{sync}",
                               lambda: coll(g_pp, state), kernels=None,
                               interpret_ok=interpret_ok)
        # the vmap reference runs on one chip: XLA cannot partition a
        # Mosaic kernel over a sharded operand
        ref = jax.jit(lambda g, s, comp=comp: comp.compress_per_pod(
            g, s, step=0)[:2])
        g_r, s_r = ref(*jax.device_put((g_pp, state), one))
        out.append(_check(res, max(_tree_err(g_c, g_r),
                                   _tree_err(s_c, s_r))))
    return out


def phase_project_sharded(sz: Sizes, key, mesh, *,
                          interpret_ok: bool = False) -> list:
    import jax
    from repro import rp
    dims = sz.orders[0]
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=sz.k, dims=dims, rank=sz.rank),
        jax.random.fold_in(key, 0))
    x = jax.random.normal(jax.random.fold_in(key, 1), (sz.buckets,) + dims)
    y, res = _run("pod/project_sharded",
                  lambda: rp.project_sharded(op, x, mesh=mesh), kernels=1,
                  interpret_ok=interpret_ok)
    return [_check(res, _rel_err(y, rp.project(op, x, backend="auto")))]


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pod-axis sync and the sharded "
                         "projection across four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import CacheHits, enable_compile_cache
    cache_dir = enable_compile_cache()
    key = jax.random.PRNGKey(0)
    sz = Sizes()
    kind = dev.device_kind
    if args.chips == 4:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",), devices=devices[:4])
        phases = [lambda: phase_pod_sync(sz, key, mesh),
                  lambda: phase_project_sharded(sz, key, mesh)]
    else:
        phases = [lambda: phase_dense(sz, key), lambda: phase_struct(sz, key),
                  lambda: phase_serve(sz, key), lambda: phase_update(sz, key)]
    for phase in phases:
        for r in phase():
            print(r.line(kind), flush=True)
    print(f"compile cache: dir={cache_dir} hits={CacheHits.count}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
