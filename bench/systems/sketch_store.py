"""System under test: the sketch-serving engine over a large sketch store.

Set-up fills the store with the corpus's sketches: seeded rows drawn on the
device in one call (each a Gaussian N(0, 1/k) sketch, as a JL sketch of a
unit-norm item is), added with `SketchStore.add` in two parts, so that the
host array has the capacity that growth by doubling gives it. The store
lives on the host, as the program keeps it.

Two request kinds, both driven on the wall clock:

* `ingest` (open loop): a payload (a dense tensor, a short flat vector, or
  a TT or CP tensor) goes through `SketchServer.submit`; `tick` flushes one
  lane (one projection dispatch) and adds its sketches to the store. A
  request is done when its sketch is in the store.
* `query` (closed loop): one top-m `SketchServer.query` of one (k,) sketch,
  the next as soon as the last returns. Its sketch is a stored row plus
  noise (a near-duplicate of that item).

The comparison, after the window:

* ingest, `sketch_err`: for a seeded sample of the window's requests, the
  row stored under the returned id against the reference projection of
  the payload (densified in float64, contracted at HIGHEST), as
  ||s - s_ref|| / ||s_ref||, the worst of the sample;
* query, `query_err`: over every returned (id, dist2), the larger of the
  gap between `dist2` and the exact float32 squared distance of that id,
  and the excess of that exact distance over the exact m-th distance,
  both over the exact m-th distance, computed from the benchmark's own
  rows. A wrong distance and a wrong id (one outside the exact top m)
  both show in it;
* query, `query_order`: the answers whose ids are not all distinct or
  whose distances do not rise, an exact count.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench import work as W
from bench.arrivals import jax_key, rng_for

CHECK_SAMPLE = 1024


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fill_rows(seed: int, n: int, k: int) -> np.ndarray:
    """The corpus: n seeded (k,) sketches, drawn on the device in one call."""
    key = jax.random.fold_in(jax_key(seed), 8)
    rows = jax.jit(lambda kk: jax.random.normal(kk, (n, k), jnp.float32)
                   / np.float32(math.sqrt(k)))(key)
    return np.asarray(rows)


def grown_capacity(n: int) -> int:
    """The rows a store holds room for once it has grown to n by doubling
    from its first allocation of 1024."""
    cap = 1024
    while cap < n:
        cap *= 2
    return cap


class SketchStoreSystem:
    span = "query"      # the call a closed loop makes
    OPS = ("ingest", "query")

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro import rp
        from repro.serve import ServeConfig, SketchServer, SketchStore

        self.cfg, self.mix, self.seed = cfg, mix, seed
        a = cfg["assumed"]
        sp = a["projector"]
        self.spec = rp.ProjectorSpec(family=sp["family"], k=sp["k"],
                                     dims=tuple(sp["dims"]), rank=sp["rank"])
        self.dims, self.k, self.rank = self.spec.dims, self.spec.k, sp["rank"]
        self.op_seed = int(a["operator_seed"])
        self.server = SketchServer(
            ServeConfig(**a["serve"]),
            SketchStore(self.spec, query_tile=a["serve"]["query_tile"]))
        self.server.cache.get(self.spec, self.op_seed)
        self.n_items = int(cfg["store_items"])
        t0 = time.perf_counter()
        self.rows = fill_rows(seed, self.n_items, self.k)
        t1 = time.perf_counter()
        half = grown_capacity(self.n_items) // 2
        store = self.server.store
        parts = ([self.rows[:half], self.rows[half:]]
                 if self.n_items > half else [self.rows])
        for part in parts:
            store.add(part)
        log(f"set-up: corpus of {self.n_items} rows {t1 - t0:.3f} s, "
            f"store fill {time.perf_counter() - t1:.3f} s")
        self.reqs = []
        self.payloads = {}
        self.qres = {}
        self.rid_to_idx = {}
        self.store_id = {}
        self.n_done = 0
        self.ticks = 0

    # -- set-up: the window's payloads, and every shape they will use ---------
    def _payload(self, r):
        from repro.core.formats import CPTensor, TTTensor
        rng = rng_for(self.seed, 3, r.idx)
        d = self.dims
        p = r.params
        kind = p["payload"]
        if kind == "dense_full":
            return rng.standard_normal(d, np.float32)
        if kind == "dense_flat":
            return rng.standard_normal(p["length"], np.float32)
        if kind == "tt":
            ranks = [1] + [p["rank"]] * (len(d) - 1) + [1]
            return TTTensor(tuple(
                rng.standard_normal((ranks[n], dn, ranks[n + 1]), np.float32)
                / np.float32(math.sqrt(dn * ranks[n]))
                for n, dn in enumerate(d)))
        if kind == "cp":
            return CPTensor(tuple(
                rng.standard_normal((dn, p["rank"]), np.float32)
                / np.float32(math.sqrt(dn)) for dn in d))
        raise ValueError(f"unknown payload kind {kind!r}")

    def _query(self, r) -> np.ndarray:
        """A near-duplicate of stored item `item`: its row plus noise of
        `noise` times the row's RMS."""
        row = self.rows[r.params["item"]]
        rms = float(np.sqrt(np.mean(row.astype(np.float64) ** 2)))
        noise = rng_for(self.seed, 4, r.idx).standard_normal(self.k)
        return (row + np.float32(r.params["noise"] * rms)
                * noise.astype(np.float32)).astype(np.float32)

    def prepare(self, reqs) -> None:
        """Make the window's payloads and warm every shape they use."""
        bad = {r.op for r in reqs} - set(self.OPS)
        if bad:
            raise ValueError(f"{type(self).__name__} serves {self.OPS}, "
                             f"not {sorted(bad)}")
        self.reqs = reqs
        self.payloads, self.qres, self.store_id = {}, {}, {}
        self.rid_to_idx = {}
        self.n_calls = 0
        t0 = time.perf_counter()
        for r in reqs:
            self.payloads[r.idx] = (self._payload(r) if r.op == "ingest"
                                    else self._query(r))
        t1 = time.perf_counter()
        self._warm(reqs)
        log(f"set-up: payloads {t1 - t0:.3f} s, warm-up "
            f"{time.perf_counter() - t1:.3f} s")

    def _warm(self, reqs) -> None:
        """Every shape the window can dispatch: each structure at each batch
        size up to `max_batch` and each largest input rank, then every
        payload shape (each flat length, each rank) once more; then one
        query. Their sketches join the store, as any ingest would."""
        mb = self.server.cfg.max_batch
        groups: dict = {}
        for r in reqs:
            if r.op != "ingest":
                continue
            kind = r.params["payload"]
            groups.setdefault("dense" if kind.startswith("dense") else kind,
                              []).append(r)
        t = 0.0

        def send(batch):
            nonlocal t
            for r in batch:
                self.server.submit(self.payloads[r.idx], self.spec,
                                   seed=self.op_seed, now=t)
            self.server.tick(t, force=True)
            t += 1.0

        def shape(r):
            p = r.params
            return p["payload"], p.get("rank"), p.get("length")

        for s, rs in groups.items():
            tops = (sorted({r.params["rank"] for r in rs}) if s != "dense"
                    else [None])
            for maxr in tops:
                fits = [r for r in rs
                        if maxr is None or r.params["rank"] <= maxr]
                # one request of each payload shape, the largest rank first
                reps: dict = {}
                for r in fits:
                    reps.setdefault(shape(r), r)
                lead = sorted(reps.values(), key=lambda r: (
                    r.params.get("rank") != maxr, str(shape(r))))
                pool = lead + [r for r in fits if r not in lead]
                for n in range(1, mb + 1):
                    send([pool[j % len(pool)] for j in range(n)])
                # the shapes a batch size above did not reach, each led by
                # one of the largest rank
                rest = lead[mb:]
                for j in range(0, len(rest), mb - 1):
                    send(lead[:1] + rest[j:j + mb - 1])
        if any(r.op == "query" for r in reqs):
            q = next(r for r in reqs if r.op == "query")
            self.server.query(self.payloads[q.idx], q.params["top_m"])
        self.n_done = len(self.server.done)

    # -- the window -------------------------------------------------------------
    def submit(self, r, now: float) -> None:
        if r.op != "ingest":
            raise ValueError(f"an open loop here sends ingests, not {r.op!r}")
        req = self.server.submit(self.payloads[r.idx], self.spec,
                                 seed=self.op_seed, now=now * 1e6)
        self.rid_to_idx[req.rid] = r.idx

    def pending(self) -> int:
        return self.server.batcher.pending()

    def ready(self, now: float) -> bool:
        return self.server.batcher.ready(now * 1e6)

    def next_wake(self):
        d = self.server.batcher.next_deadline()
        return None if d is None else d * 1e-6

    def serve(self, now: float, spans):
        """One call into the program: one tick."""
        with spans("tick"):
            self.server.tick(now * 1e6)
        self.ticks += 1
        new = self.server.done[self.n_done:]
        self.n_done = len(self.server.done)
        served = []
        for req in new:
            idx = self.rid_to_idx[req.rid]
            self.store_id[idx] = req.store_id
            served.append(idx)
        return served, []

    def step(self) -> None:
        """A closed loop's call: the next query of the list, cycling."""
        r = self.reqs[self.n_calls % len(self.reqs)]
        self.n_calls += 1
        if r.op != "query":
            raise ValueError(f"a closed loop here sends queries, not {r.op!r}")
        res = self.server.query(self.payloads[r.idx], r.params["top_m"])
        self.qres[r.idx] = (np.asarray(res.ids), np.asarray(res.dist2))

    def work_per_step(self) -> W.Work:
        return W.Work()

    # -- what the window did --------------------------------------------------
    def work_of(self, idxs) -> W.Work:
        """The projection work of the ingests among `idxs`, with the
        operator read once per tick."""
        fam, d, k, r = self.spec.family, self.dims, self.k, self.rank
        total = W.Work()
        size = math.prod(d)
        for i in idxs:
            q = self.reqs[i]
            if q.op != "ingest":
                continue
            kind = q.params["payload"]
            if kind.startswith("dense"):
                dense = W.project_dense(fam, d, k, r, 1)
                total += W.Work(dense.flops, W.F32 * (size + k))
            else:
                total += W.project_struct(fam, kind, d, k, r,
                                          q.params["rank"])
        return total + W.Work(0.0, W.operator_bytes(fam, d, k, r)) * self.ticks

    def counters(self) -> dict:
        from repro import rp
        st = rp.plan_cache_stats()
        return {"plan_builds": st.builds, "ticks": self.ticks,
                "store_rows": len(self.server.store)}

    def close(self) -> None:
        pass

    # -- the comparison -------------------------------------------------------
    def check(self, control=None) -> dict:
        """The numbers compared. `control` is a lower matmul precision: the
        reference at that precision then takes the program's place."""
        out = {}
        ingests = sorted(self.store_id)
        if ingests:
            out["sketch_err"] = self._check_ingest(ingests, control)
        if self.qres:
            out.update(self._check_query(control))
        return out

    def _sample(self, idxs):
        if len(idxs) <= CHECK_SAMPLE:
            return list(idxs)
        pick = rng_for(self.seed, 5).choice(len(idxs), CHECK_SAMPLE,
                                            replace=False)
        return [idxs[i] for i in sorted(pick)]

    def _dense(self, idx) -> np.ndarray:
        from repro.core.formats import CPTensor, TTTensor
        p = self.payloads[idx]
        if isinstance(p, TTTensor):
            return ref.tt_full(p.cores)
        if isinstance(p, CPTensor):
            return ref.cp_full(p.factors)
        flat = np.zeros(math.prod(self.dims))
        flat[:p.size] = np.asarray(p, np.float64).reshape(-1)
        return flat.reshape(self.dims)

    def operator(self):
        key = jax.random.PRNGKey(self.op_seed)
        if self.spec.family == "tt":
            return ref.tt_cores(key, self.dims, self.k, self.rank), \
                ref.tt_project
        return ref.cp_factors(key, self.dims, self.k, self.rank), \
            ref.cp_project

    def _check_ingest(self, idxs, control) -> float:
        idxs = self._sample(idxs)
        cores, project = self.operator()
        f = jax.jit(lambda x: project(cores, x, ref.HIGHEST))
        g = jax.jit(lambda x: project(cores, x, control))
        worst = 0.0
        for s in range(0, len(idxs), 64):
            chunk = idxs[s:s + 64]
            x = jnp.asarray(np.stack([self._dense(i) for i in chunk])
                            .astype(np.float32))
            want = np.asarray(f(x), np.float64)
            if control is None:
                got = self.server.store.get(
                    [self.store_id[i] for i in chunk]).astype(np.float64)
            else:
                got = np.asarray(g(x), np.float64)
            err = (np.linalg.norm(got - want, axis=1)
                   / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
            worst = max(worst, float(err.max()))
        return worst

    def _check_query(self, control) -> dict:
        if len(self.server.store) != self.n_items:
            raise ValueError("the query comparison reads the corpus alone; "
                             "the store holds more")
        idxs = sorted(self.qres)
        rows = jnp.asarray(self.rows)
        q = jnp.asarray(np.stack([self.payloads[i] for i in idxs]))
        m = self.reqs[idxs[0]].params["top_m"]
        exact = exact_dist2(rows, q)
        kth = np.partition(exact, m - 1, axis=1)[:, m - 1]
        if control is not None:
            got = brute_top(rows, q, m, control)
        worst, disorder = 0.0, 0
        for j, i in enumerate(idxs):
            ids, d2 = self.qres[i] if control is None else got[j]
            ids, d2 = np.asarray(ids), np.asarray(d2)
            e = exact[j, ids]
            scale = max(float(kth[j]), 1e-30)
            worst = max(worst, float(np.max(np.abs(d2 - e))) / scale,
                        float(np.max(e) - kth[j]) / scale)
            disorder += int(len(np.unique(ids)) != len(ids)
                            or bool(np.any(np.diff(d2) < 0)))
        return {"query_err": worst, "query_order": float(disorder)}


def exact_dist2(rows, q, block: int = 1 << 15) -> np.ndarray:
    """(Q, n) squared distances, each the sum of squared float32
    differences, in blocks of rows and queries on the device."""
    @jax.jit
    def blk(x, qq):
        d = x[None, :, :] - qq[:, None, :]
        return jnp.sum(d * d, axis=-1)

    n = rows.shape[0]
    pad = -n % block
    rows = jnp.concatenate([rows, jnp.zeros((pad, rows.shape[1]),
                                            rows.dtype)])
    out = []
    for s in range(0, q.shape[0], 8):
        qq = q[s:s + 8]
        out.append(np.concatenate([np.asarray(blk(rows[b:b + block], qq))
                                   for b in range(0, n + pad, block)],
                                  axis=1)[:, :n])
    return np.concatenate(out)


def brute_top(rows, q, m, precision) -> list:
    """Top-m by ||q||^2 - 2 q.x + ||x||^2 with the matmul at `precision`:
    the reference in a store sweep's place."""
    @jax.jit
    def top(qq, rows):
        dots = ref.einsum("qk,nk->qn", qq, rows, precision)
        d2 = (jnp.sum(qq * qq, -1)[:, None] - 2.0 * dots
              + jnp.sum(rows * rows, -1)[None])
        neg, ids = jax.lax.top_k(-d2, m)
        return ids, jnp.maximum(-neg, 0.0)

    out = []
    for s in range(0, q.shape[0], 8):
        ids, d2 = top(q[s:s + 8], rows)
        out.extend(zip(np.asarray(ids), np.asarray(d2)))
    return out


def build(cfg, mix, seed):
    return SketchStoreSystem(cfg, mix, seed)
