"""Paper Fig. 2 + App. B.2: embedding time for medium-order inputs given in
TT or CP format, across the map family (TT/CP/sparse/dense) — plus the
batched-vs-per-bucket kernel comparison that tracks the sketcher hot path
(launch counts, wall time, analytic bytes moved), the TT-vs-CP-vs-order
frontier (time/order/* rows, N in {2,3,4,5}), the compressed-domain
structured-input rows (struct/{tt,cp}x{tt,cp}/N={3,4}: carry-sweep launch
counts, carry bytes, analytic speedup), the sharded-engine rows
(shard/*: compress_collective wire bytes per sync mode + measured HLO
all-reduce bytes, project_sharded per-device bucket counts), and the
kernel perf-frontier rows (perf/*: double-buffered pipelining vs serial,
fused unsketch+EF+AdamW vs the unfused chain, int8 vs fp32 wire — see
`_perf_rows`) into BENCH_rp.json."""
import jax
import jax.numpy as jnp

from repro import rp
from repro.core import (BatchedCPTensor, BatchedTTTensor, random_cp,
                        random_tt)

from ._util import csv_row, time_call


def _compiled_with_dispatch_count(fn, *args):
    """(compiled executable, Pallas dispatches traced) for fn(*args)."""
    c0 = rp.kernel_call_count()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, rp.kernel_call_count() - c0


def _kernel_plan(direction, family, k, b, dims, rank, *, pipeline="serial"):
    """The pinned-kernel-route `ExecutionPlan` of ONE batched launch.

    All analytic values in these rows (hbm bytes, flops, params, variance
    factors, grid shapes) are read from `plan.cost` / the plan's tiles —
    the SAME resolver every dispatch goes through — so the bench rows, the
    rooflines, and the kernels' own schedules can never disagree on what a
    launch streams.
    """
    sig = rp.StructureSig(
        structure="sketch" if direction == "reconstruct" else "dense",
        batch=b)
    return rp.plan_execution(
        rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank), sig,
        kind=direction, backend="pallas", pipeline=pipeline)


def _analytic_hbm_bytes(direction, family, k, b, dims, rank):
    """Grid-accurate analytic HBM traffic of ONE batched launch, any order
    (the plan ledger of the schedule the launch would actually use)."""
    return _kernel_plan(direction, family, k, b, dims, rank).cost.hbm_bytes


def _order_frontier(rows, fast=True):
    """The TT-vs-CP-vs-order frontier the order-N kernel layer unlocks.

    One batched Pallas (interpret off-TPU) launch per (family, N, direction)
    for N in {2,..,5} at fixed k/rank: `params` shows the operator shrinking
    as the same-size bucket is tensorized into more, smaller modes (core
    params scale with the SUM of the modes, not their product), and
    `var_factor` / `var_ratio_cp_tt` chart the Thm-1 cost CP pays for that
    at each order. `launches_*` prove the mode-sweep route (one dispatch per
    batched call at every order). Wall-clock is meaningful on TPU, noisy in
    CPU interpret mode.
    """
    del fast
    k, rank, b = 128, 2, 4
    dims_by_n = {2: (64, 64), 3: (16, 16, 16), 4: (8, 8, 8, 8),
                 5: (8, 8, 8, 8, 8)}
    key = jax.random.PRNGKey(7)
    for n, dims in dims_by_n.items():
        xb = jax.random.normal(jax.random.fold_in(key, n), (b,) + dims)
        # the Thm-1 CP/TT ratio is the quotient of the two plans' ledgers
        eplans = {fam: _kernel_plan("project", fam, k, b, dims, rank)
                  for fam in ("tt", "cp")}
        var_ratio = (eplans["cp"].cost.var_factor
                     / eplans["tt"].cost.var_factor)
        for family in ("tt", "cp"):
            op = rp.make_projector(
                rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
                jax.random.fold_in(key, 10 * n))

            def project(a, op=op):
                return rp.project(op, a, backend="pallas")

            def reconstruct(y, op=op):
                return rp.reconstruct(op, y, backend="pallas")

            f_p, launches_p = _compiled_with_dispatch_count(project, xb)
            us_p = time_call(f_p, xb)
            yb = f_p(xb)
            f_r, launches_r = _compiled_with_dispatch_count(reconstruct, yb)
            us_r = time_call(f_r, yb)
            cost = eplans[family].cost
            rows.append(csv_row(
                f"time/order/{family}/N={n}", us_p,
                f"dims={'x'.join(map(str, dims))};k={k};rank={rank};B={b};"
                f"launches_project={launches_p};"
                f"launches_reconstruct={launches_r};"
                f"us_reconstruct={us_r:.1f};"
                f"params={cost.params};"
                f"var_factor={cost.var_factor:.2f};"
                f"var_ratio_cp_tt={var_ratio:.2f}"))


def _struct_frontier(rows, fast=True):
    """Compressed-domain engine rows: struct/{tt,cp}x{tt,cp}/N={3,4}.

    One batched carry-sweep Pallas (interpret off-TPU) launch per
    (operator family, input family, order) — the four structured pairings
    `rp.project` routes through `kernels/struct/`. Each row records the
    dispatch count (`launches_project`, must stay 1 per batched call — the
    bench gate's launch keys cover it), the carried bond-state bytes
    (`carry_bytes` = B·k·R·R~ floats, the memory that replaces the dense
    sweep's (B, k, d2..dN) intermediates), operator `params`, and the
    ANALYTIC dense/structured FLOP ratio (`analytic_speedup`,
    `theory.struct_speedup`) so the record carries the model's prediction
    next to measured wall-clock (meaningful on TPU, noisy in CPU interpret
    mode).
    """
    del fast
    k, r_op, r_in, b = 128, 2, 4, 4
    dims_by_n = {3: (16, 16, 16), 4: (8, 8, 8, 8)}
    key = jax.random.PRNGKey(11)
    for n, dims in dims_by_n.items():
        for in_family in ("tt", "cp"):
            mk = random_tt if in_family == "tt" else random_cp
            items = [mk(jax.random.fold_in(key, 100 * n + i), dims, r_in)
                     for i in range(b)]
            stack = (BatchedTTTensor.stack if in_family == "tt"
                     else BatchedCPTensor.stack)
            xb = stack(items)
            for op_family in ("tt", "cp"):
                op = rp.make_projector(
                    rp.ProjectorSpec(family=op_family, k=k, dims=dims,
                                     rank=r_op),
                    jax.random.fold_in(key, 10 * n))

                def project(x, op=op):
                    return rp.project(op, x, backend="pallas")

                f, launches = _compiled_with_dispatch_count(project, xb)
                us = time_call(f, xb)
                # the plan the dispatch above resolved (a cache hit here);
                # analytic_speedup is its dense counterpart's flops over its
                # own — the same quotient theory.struct_speedup charts
                ep = rp.plan_execution(
                    op, rp.StructureSig(structure=in_family, batch=b,
                                        in_rank=r_in), backend="pallas")
                speedup = (_kernel_plan("project", op_family, k, b, dims,
                                        r_op).cost.flops / ep.cost.flops)
                rows.append(csv_row(
                    f"struct/{op_family}x{in_family}/N={n}", us,
                    f"dims={'x'.join(map(str, dims))};k={k};B={b};"
                    f"r_op={r_op};r_in={r_in};"
                    f"launches_project={launches};"
                    f"carry_bytes={ep.carry_bytes};"
                    f"params={ep.cost.params};"
                    f"flops_struct={ep.cost.flops // b};"
                    f"analytic_speedup={speedup:.1f}x"))


def _shard_rows(rows, fast=True):
    """Sharded sketching engine rows (shard/*).

    Runs the `compress_collective` cross-pod compressed all-reduce and the
    `project_sharded` bucket-axis path on a pod mesh over EVERY available
    device (1 on the plain CI job, 8 under the multi-device job's
    XLA_FLAGS=--xla_force_host_platform_device_count=8). Row names and the
    gated trace-time launch counts are device-count-independent, so
    `check_regression` can diff records across both jobs; per-device bucket
    counts, npod, the analytic wire bytes of the active sync mode, and the
    MEASURED HLO all-reduce bytes (the pmean's channel all-reduce op is
    retained even on a 1-device mesh, so the bytes match across jobs; only
    the replica-group size differs) land in `derived` for the record.
    """
    del fast
    from repro.core.sketch import PytreeSketcher, SketchConfig
    from repro.launch.roofline import parse_collectives
    from repro.optim.compress import SketchCompressor

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("pod",))
    cfg = SketchConfig(family="tt", k=128, rank=2, bucket_elems=8 * 16 * 16,
                       dims=(8, 16, 16))
    key = jax.random.PRNGKey(23)
    g = {"w": jax.random.normal(jax.random.fold_in(key, 0), (ndev, 4096)),
         "b": jax.random.normal(jax.random.fold_in(key, 1), (ndev, 100))}
    state = {"residual": jax.tree.map(jnp.zeros_like, g)}
    sk = PytreeSketcher(cfg, jax.tree.map(lambda x: x[0], g))
    for sync in ("sketch-mean", "local-mean"):
        comp = SketchCompressor(cfg, sync=sync, pod_axis="pod")

        def run_step(gg, ss, step, comp=comp):
            # metrics dropped so their telemetry reductions DCE away and
            # the HLO collective count is exactly the sync pmean
            with rp.force_pallas():
                return comp.compress_collective(gg, ss, step=step,
                                                mesh=mesh)[:2]

        f, launches = _compiled_with_dispatch_count(run_step, g, state, 0)
        us = time_call(f, g, state, 0)
        ar = parse_collectives(f.as_text())["per_type"].get(
            "all-reduce", {"count": 0, "bytes": 0.0})
        wire = comp.wire_bytes(sk)      # the plan layer's wire ledger
        rows.append(csv_row(
            f"shard/collective/sync={sync}", us,
            f"npod={ndev};n_buckets={sk.n_buckets};k={cfg.k};"
            f"launches_project={launches};"
            f"wire_bytes={wire};"
            f"hlo_allreduce_bytes={int(ar['bytes'])};"
            f"hlo_allreduce_count={ar['count']}"))

    nb = 16
    op = rp.make_projector(
        rp.ProjectorSpec(family="tt", k=128, dims=(8, 16, 16), rank=2),
        jax.random.fold_in(key, 2))
    xb = jax.random.normal(jax.random.fold_in(key, 3), (nb, 8, 16, 16))

    def proj(x):
        with rp.force_pallas():
            return rp.project_sharded(op, x, mesh=mesh)

    f_p, launches_p = _compiled_with_dispatch_count(proj, xb)
    us_p = time_call(f_p, xb)
    rows.append(csv_row(
        f"shard/project/B={nb}", us_p,
        f"npod={ndev};buckets_per_device={nb // ndev};"
        f"launches_project={launches_p};k=128"))


def _batched_vs_per_bucket(rows, fast=True):
    """One batched launch per leaf vs the per-bucket formulations.

    A 16-bucket "leaf" runs through three schedules per direction:
      * per_bucket — one `pallas_call` dispatch per bucket (a Python loop of
        16 single-bucket calls): the per-bucket launch count the batch axis
        exists to eliminate;
      * vmap — `jax.vmap` over single-bucket kernels, the pre-batch sketcher
        formulation (one dispatch at trace time; the batch dim is grafted on
        by the vmap batching rule rather than placed by the BlockSpecs);
      * batched — the native batch grid axis: ONE dispatch, cores streamed
        once per k-tile.
    Launch counts come from rp.kernel_call_count() (dispatch-time
    instrumentation); bytes are the grid-accurate analytic HBM traffic of
    the per-bucket vs batched schedules (_analytic_hbm_bytes — the
    per-bucket schedule re-streams the whole operator every bucket, the
    batched grid amortizes core fetches over the batch tile). Wall-clock
    `speedup` is batched vs vmap — meaningful on TPU, noisy in CPU
    interpret mode.
    """
    nb = 16                      # the acceptance-criteria bucket count
    dims = (8, 16, 16) if fast else (32, 64, 32)
    k = 128
    rank = 2
    key = jax.random.PRNGKey(0)
    xb = jax.random.normal(jax.random.fold_in(key, 1), (nb,) + dims)
    for family in ("tt", "cp"):
        op = rp.make_projector(
            rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
            jax.random.fold_in(key, 2))

        def apply(direction, y_or_x, op=op):
            fn = rp.project if direction == "project" else rp.reconstruct
            return fn(op, y_or_x, backend="auto")

        for direction, inp in (("project", xb),
                               ("reconstruct", apply("project", xb))):
            def per_bucket(a, d=direction):
                with rp.force_pallas():
                    return jnp.stack([apply(d, a[i]) for i in range(nb)])

            def vmapped(a, d=direction):
                with rp.force_pallas():
                    return jax.vmap(lambda t: apply(d, t))(a)

            def batched(a, d=direction):
                with rp.force_pallas():
                    return apply(d, a)

            f_pb, launches_pb = _compiled_with_dispatch_count(per_bucket, inp)
            f_vm, launches_vm = _compiled_with_dispatch_count(vmapped, inp)
            f_b, launches_b = _compiled_with_dispatch_count(batched, inp)
            us_pb = time_call(f_pb, inp)
            us_vm = time_call(f_vm, inp)
            us_b = time_call(f_b, inp)
            bytes_pb = nb * _analytic_hbm_bytes(direction, family, k, 1,
                                                dims, rank)
            bytes_b = _analytic_hbm_bytes(direction, family, k, nb,
                                          dims, rank)
            rows.append(csv_row(
                f"time/batched/{family}/{direction}/B={nb}", us_b,
                f"launches_batched={launches_b};"
                f"launches_per_bucket={launches_pb};"
                f"launches_vmap={launches_vm};"
                f"launch_reduction={launches_pb / max(1, launches_b):.1f}x;"
                f"us_per_bucket_path={us_pb:.1f};us_vmap_path={us_vm:.1f};"
                f"speedup={us_vm / us_b:.2f}x;"
                f"bytes_batched={bytes_b};bytes_per_bucket={bytes_pb}"))


def _dense_entry_fusions(hlo_text, shape):
    """Standalone dense elementwise kernels in the ENTRY computation.

    Counts optimized-HLO `fusion` ops in ENTRY whose result is the full
    dense `shape` — the EF/AdamW elementwise passes XLA launches as their
    own kernels in the unfused chain and that disappear entirely into the
    Pallas launch in the fused one (0 vs 4 on the bench shapes; the gate
    pins the fused count staying at 0 via the perf row's derived keys).
    """
    import re
    entry = re.search(r"ENTRY [^{]+\{(.*?)\n\}", hlo_text, re.S)
    if entry is None:
        return -1
    sig = "f32[" + ",".join(map(str, shape)) + "]"
    return sum(1 for line in entry.group(1).splitlines()
               if " fusion(" in line and line.lstrip().split(" = ")[-1]
               .startswith(sig))


def _perf_rows(rows, fast=True):
    """Kernel perf frontier rows (perf/*) — the wall-clock-gated trio.

    * perf/pipeline/sweep/{tt,cp} and perf/pipeline/carry/{tt,cp} — the
      double-buffered DMA schedule vs the serial one on shapes with real
      overlap to win (d1/ba > 1 grid steps for the sweep, b/tb > 1 for the
      carry). `speedup` is a PLAIN float (serial us / pipelined us) so the
      gate can band it; in CPU interpret mode the DMA emulation makes it
      hover near 1.0 — the 0.5x relative band catches collapses, TPU runs
      show the overlap.
    * perf/fused/update/{tt,cp} — ONE fused unsketch+EF+AdamW launch vs
      the unfused reconstruct -> EF -> AdamW chain on the same buckets.
      `speedup` (unfused us / fused us) rides the same band; `hbm_ratio`
      (fused/unfused analytic bytes from the planner ledger, < 1) and the
      standalone dense elementwise kernel counts (`dense_kernels_fused=0`
      vs `dense_kernels_unfused=4` — the EF/AdamW passes XLA launches as
      its own fusions collapse into the Pallas call) are deterministic.
    * perf/wire/sync={sketch-mean,local-mean} — compress_collective with
      wire='fp32' vs wire='int8': measured HLO all-reduce bytes for both,
      `wire_ratio` = fp32/int8 bytes (~3.9x: int8 payload + fp32 scales),
      and the compressor's own analytic `wire_bytes` for the int8 mode so
      the measured and declared ledgers sit side by side.
    """
    del fast
    key = jax.random.PRNGKey(31)

    # --- double-buffered dense sweep vs serial --------------------------
    k, rank, b = 128, 2, 8
    dims = (256, 16, 16)                   # d1/ba > 1: steps to overlap
    xb = jax.random.normal(jax.random.fold_in(key, 0), (b,) + dims)
    for family in ("tt", "cp"):
        op = rp.make_projector(
            rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank),
            jax.random.fold_in(key, 1))

        def serial(a, op=op):
            return rp.project(op, a, backend="pallas")

        def double(a, op=op):
            return rp.project(op, a, backend="pallas", pipeline="double")

        f_s, _ = _compiled_with_dispatch_count(serial, xb)
        f_d, launches_d = _compiled_with_dispatch_count(double, xb)
        us_s, us_d = time_call(f_s, xb), time_call(f_d, xb)
        ep = _kernel_plan("project", family, k, b, dims, rank,
                          pipeline="double")
        rows.append(csv_row(
            f"perf/pipeline/sweep/{family}", us_d,
            f"dims={'x'.join(map(str, dims))};k={k};B={b};"
            f"launches_project={launches_d};us_serial={us_s:.1f};"
            f"speedup={us_s / us_d:.3f};"
            f"hbm_bytes={ep.cost.hbm_bytes};"
            f"grid_steps={-(-dims[0] // ep.tiles[2])}"))

    # --- double-buffered carry sweep vs serial --------------------------
    bc, r_in, cdims = 64, 4, (16, 16, 16)  # b/tb > 1: steps to overlap
    items = [random_tt(jax.random.fold_in(key, 50 + i), cdims, r_in)
             for i in range(bc)]
    xc = BatchedTTTensor.stack(items)
    for family in ("tt", "cp"):
        op = rp.make_projector(
            rp.ProjectorSpec(family=family, k=k, dims=cdims, rank=rank),
            jax.random.fold_in(key, 2))

        def serial(a, op=op):
            return rp.project(op, a, backend="pallas")

        def double(a, op=op):
            return rp.project(op, a, backend="pallas", pipeline="double")

        f_s, _ = _compiled_with_dispatch_count(serial, xc)
        f_d, launches_d = _compiled_with_dispatch_count(double, xc)
        us_s, us_d = time_call(f_s, xc), time_call(f_d, xc)
        ep = rp.plan_execution(
            op, rp.StructureSig(structure="tt", batch=bc, in_rank=r_in),
            backend="pallas", pipeline="double")
        rows.append(csv_row(
            f"perf/pipeline/carry/{family}", us_d,
            f"dims={'x'.join(map(str, cdims))};k={k};B={bc};r_in={r_in};"
            f"launches_project={launches_d};us_serial={us_s:.1f};"
            f"speedup={us_s / us_d:.3f};"
            f"hbm_bytes={ep.cost.hbm_bytes};"
            f"grid_steps={-(-bc // ep.tiles[1])}"))

    # --- fused unsketch+EF+AdamW vs the unfused chain -------------------
    from repro.kernels import fused_update_buckets
    nb, fdims = 8, (64, 16, 16)
    yb = jax.random.normal(jax.random.fold_in(key, 3), (nb, k))
    dense = [jax.random.normal(jax.random.fold_in(key, 60 + i),
                               (nb,) + fdims) for i in range(4)]
    lr = jnp.float32(1e-3)
    c1 = c2 = jnp.float32(0.5)
    hp = dict(alpha=0.9, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    for family in ("tt", "cp"):
        op = rp.make_projector(
            rp.ProjectorSpec(family=family, k=k, dims=fdims, rank=rank),
            jax.random.fold_in(key, 4))

        interpret = rp.plan_update(op, nb, fused=True).interpret

        def fused(y, p, w, m, v, lr, c1, c2, op=op, interpret=interpret):
            rp.count_kernel_dispatch(interpret=interpret)
            return fused_update_buckets(op, y, p, w, m, v, lr, c1, c2,
                                        interpret=interpret, **hp)

        def unfused(y, p, w, m, v, lr, c1, c2, op=op):
            with rp.force_pallas():
                g = hp["alpha"] * rp.reconstruct(op, y)
            resid = p - g
            m32 = hp["b1"] * m + (1 - hp["b1"]) * g
            v32 = hp["b2"] * v + (1 - hp["b2"]) * g * g
            step = (m32 / c1) / (jnp.sqrt(v32 / c2) + hp["eps"])
            return resid, w - lr * (step + hp["weight_decay"] * w), m32, v32

        argv = (yb, *dense, lr, c1, c2)
        f_f, launches_f = _compiled_with_dispatch_count(fused, *argv)
        f_u, launches_u = _compiled_with_dispatch_count(unfused, *argv)
        us_f, us_u = time_call(f_f, *argv), time_call(f_u, *argv)
        fus_f = _dense_entry_fusions(f_f.as_text(), (nb,) + fdims)
        fus_u = _dense_entry_fusions(f_u.as_text(), (nb,) + fdims)
        hbm_f = rp.plan_update(op, nb, fused=True).cost.hbm_bytes
        hbm_u = rp.plan_update(op, nb, fused=False).cost.hbm_bytes
        rows.append(csv_row(
            f"perf/fused/update/{family}", us_f,
            f"dims={'x'.join(map(str, fdims))};k={k};B={nb};"
            f"launches_project={launches_f};launches_unfused={launches_u};"
            f"us_unfused={us_u:.1f};speedup={us_u / us_f:.3f};"
            f"hbm_ratio={hbm_f / hbm_u:.3f};"
            f"hbm_bytes_fused={hbm_f};"
            f"hbm_bytes_unfused={hbm_u};"
            f"dense_kernels_fused={fus_f};dense_kernels_unfused={fus_u}"))

    # --- int8 sketches on the wire --------------------------------------
    from repro.core.sketch import PytreeSketcher, SketchConfig
    from repro.launch.roofline import parse_collectives
    from repro.optim.compress import SketchCompressor
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("pod",))
    cfg = SketchConfig(family="tt", k=128, rank=2, bucket_elems=8 * 16 * 16,
                       dims=(8, 16, 16))
    g = {"w": jax.random.normal(jax.random.fold_in(key, 5), (ndev, 4096)),
         "b": jax.random.normal(jax.random.fold_in(key, 6), (ndev, 100))}
    state = {"residual": jax.tree.map(jnp.zeros_like, g)}
    sk = PytreeSketcher(cfg, jax.tree.map(lambda x: x[0], g))
    for sync in ("sketch-mean", "local-mean"):
        hlo_bytes = {}
        for wire in ("fp32", "int8"):
            comp = SketchCompressor(cfg, sync=sync, pod_axis="pod",
                                    wire=wire)

            def run_step(gg, ss, step, comp=comp):
                with rp.force_pallas():
                    return comp.compress_collective(gg, ss, step=step,
                                                    mesh=mesh)[:2]

            f, launches = _compiled_with_dispatch_count(run_step, g, state, 0)
            us = time_call(f, g, state, 0)
            ar = parse_collectives(f.as_text())["per_type"].get(
                "all-reduce", {"count": 0, "bytes": 0.0})
            hlo_bytes[wire] = int(ar["bytes"])
        comp_i8 = SketchCompressor(cfg, sync=sync, pod_axis="pod",
                                   wire="int8")
        rows.append(csv_row(
            f"perf/wire/sync={sync}", us,
            f"npod={ndev};n_buckets={sk.n_buckets};k={cfg.k};"
            f"launches_project={launches};"
            f"hlo_bytes_fp32={hlo_bytes['fp32']};"
            f"hlo_bytes_int8={hlo_bytes['int8']};"
            f"wire_ratio={hlo_bytes['fp32'] / max(1, hlo_bytes['int8']):.3f};"
            f"wire_bytes_int8={comp_i8.wire_bytes(sk)}"))


def run(fast=True):
    d, N = 3, 12 if fast else 12
    dims = (d,) * N
    D = d ** N
    k = 256
    key = jax.random.PRNGKey(0)
    x_tt = random_tt(key, dims, 10, norm="unit")
    x_cp = random_cp(key, dims, 10, norm="unit")
    x_dense = x_tt.full().reshape(-1)

    def op(family, fold, rank=1):
        spec = rp.ProjectorSpec(family=family, k=k, dims=dims, rank=rank)
        return rp.make_projector(spec, jax.random.fold_in(key, fold))

    tt_op = op("tt", 1, 5)
    cp_op = op("cp", 2, 25)
    rows = []

    for name, o, inp, tag in [
        ("TT(5)", tt_op, x_tt, "input=TT"),
        ("CP(25)", cp_op, x_tt, "input=TT"),
        ("TT(5)", tt_op, x_cp, "input=CP"),
        ("CP(25)", cp_op, x_cp, "input=CP"),
        ("VerySparse", op("sparse", 3), x_dense, "input=dense"),
        ("Gaussian", op("gaussian", 4), x_dense, "input=dense"),
    ]:
        f = jax.jit(lambda t, o=o: rp.project(o, t))
        rows.append(csv_row(f"time/medium/{name}/{tag}", time_call(f, inp),
                            f"k={k};D={D}"))

    # App B.2: scaling in N (input dim d^N)
    for n in ((8, 11, 12) if fast else (8, 11, 12, 13)):
        dims_n = (3,) * n
        x_n = random_tt(jax.random.fold_in(key, n), dims_n, 10)
        op_n = rp.make_projector(
            rp.ProjectorSpec(family="tt", k=k, dims=dims_n, rank=5),
            jax.random.fold_in(key, 100 + n))
        f = jax.jit(lambda t: rp.project(op_n, t))
        rows.append(csv_row(f"time/scaling/TT(5)/N={n}", time_call(f, x_n),
                            f"D={3**n}"))

    _batched_vs_per_bucket(rows, fast=fast)
    _order_frontier(rows, fast=fast)
    _struct_frontier(rows, fast=fast)
    _shard_rows(rows, fast=fast)
    _perf_rows(rows, fast=fast)
    return rows
