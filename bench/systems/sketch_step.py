"""System under test: the sketched optimizer step on one chip's share of one
decoder layer.

The step is the program's own single-pod compressed branch, wired as its
train step wires it: `SketchCompressor.compress` (sketch, unsketch, error
feedback) then `adamw.update`, jitted with the state donated. Gradients
come from a pool of seeded trees made in set-up and used in turn.

Set-up drives this one compiled step through the first three steps (the
window's own call, on distinct gradients) and keeps what the comparison
needs on the host: the moments and residual after step 1 and the
parameters after step 3. The window then runs the same object on.

The comparison, once the window has closed and the program's state is
freed, follows those three steps with the plain reference, leaf by leaf:

* `grad1`: the first gradient as the optimizer got it, worked out from
  m after step 1 as (m1 - b1 m0) / (1 - b1);
* `resid1`: the error-feedback residual after step 1;
* `param3`: the parameters' change after step 3.

Each is the worst leaf's norm of the difference from the reference, over
the larger of that leaf's reference norm and the median leaf's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench import work as W
from bench.arrivals import jax_key

KINDS = {"param": 0, "m": 1, "v": 2, "resid": 3, "grad": 4}
REF_BUCKETS = 4     # buckets per reference contraction (bounds its memory)


def leaf_shapes(cfg) -> dict:
    """This chip's share of one decoder layer under tensor parallelism:
    columns of wq/wk/wv/w_gate/w_up and rows of wo/w_down are split, norms
    are whole."""
    tp = cfg["tensor_parallel_size"]
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] // tp * hd
    kv = cfg["num_key_value_heads"] // tp * hd
    ff = cfg["intermediate_size"] // tp
    return {"attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                     "wo": (q, d)},
            "mlp": {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)},
            "norm_attn": (d,), "norm_mlp": (d,)}


def _flat_shapes(cfg):
    leaves, treedef = jax.tree.flatten(
        leaf_shapes(cfg), is_leaf=lambda t: isinstance(t, tuple))
    return [tuple(s) for s in leaves], treedef


def leaf_data(key, kind: str, i: int, shape, a: dict, slot: int = 0):
    """One leaf of one seeded tree: parameters, moments, residual, or the
    gradient of pool slot `slot`. The same call gives the same bits in the
    set-up's batched call and in the reference's leaf-by-leaf one."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, KINDS[kind]), slot), i)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "v":
        mu, sigma = a["v_lognormal"]
        return jnp.exp(mu + sigma * z)
    return a[f"{kind}_std"] * z


class SketchStep:
    span = "step"

    def __init__(self, cfg: dict, mix: dict, seed: int, *, wrap=None):
        """`wrap(step) -> step` replaces the program's step with another
        built on it: the tests plant faults with it."""
        from repro.core.sketch import SketchConfig
        from repro.optim import adamw
        from repro.optim.compress import SketchCompressor

        self.cfg, self.seed = cfg, seed
        a = self.assumed = cfg["assumed"]
        sk = a["sketch"]
        self.dims = tuple(sk["dims"])
        self.comp = SketchCompressor(
            SketchConfig(family=sk["family"], k=sk["k"], rank=sk["rank"],
                         dims=self.dims, bucket_elems=math.prod(self.dims)),
            base_key=a["operator_base_key"])
        self.acfg = adamw.AdamWConfig(
            b1=a["b1"], b2=a["b2"], eps=a["eps"],
            weight_decay=a["weight_decay"], clip_norm=None)
        self.lr = jnp.float32(a["lr"])
        self.pool = int(mix["params"]["grad_pool"])
        self.shapes, self.treedef = _flat_shapes(cfg)
        self.key = jax_key(seed)
        step = self.program_step()
        self.step_fn = jax.jit(wrap(step) if wrap else step,
                               donate_argnums=(0,))
        self.state, self.grads = jax.jit(self._make)(self.key)
        self.steps_done = 0
        self.snap = {}
        # the first three steps: compile, warm, and what the check keeps
        self.step()
        opt = self.state["opt"]
        self.snap["m1"] = [np.asarray(x) for x in jax.tree.leaves(opt["m"])]
        self.snap["e1"] = [np.asarray(x)
                           for x in jax.tree.leaves(self.state["ef"])]
        self.step()
        self.step()
        self.snap["p3"] = [np.asarray(x)
                           for x in jax.tree.leaves(self.state["params"])]

    def program_step(self):
        """The program's step, `(state, grads) -> (state, metrics)`: the
        compressed single-pod branch of its train step. An adapter for
        another variant of the step overrides this alone."""
        from repro.optim import adamw
        comp, acfg, lr = self.comp, self.acfg, self.lr

        def program_step(state, grads):
            g_hat, ef, cmet = comp.compress(grads, state["ef"],
                                            step=state["opt"]["count"])
            p, opt, omet = adamw.update(state["params"], g_hat, state["opt"],
                                        lr, acfg)
            return {"params": p, "opt": opt, "ef": ef}, {**cmet, **omet}
        return program_step

    # -- set-up -------------------------------------------------------------
    def _tree(self, key, kind, slot=0):
        return jax.tree.unflatten(self.treedef, [
            leaf_data(key, kind, i, s, self.assumed, slot)
            for i, s in enumerate(self.shapes)])

    def _make(self, key):
        state = {"params": self._tree(key, "param"),
                 "opt": {"m": self._tree(key, "m"), "v": self._tree(key, "v"),
                         "count": jnp.asarray(self.assumed["count0"],
                                              jnp.int32)},
                 "ef": {"residual": self._tree(key, "resid")}}
        grads = [self._tree(key, "grad", s) for s in range(self.pool)]
        return state, grads

    # -- the window ---------------------------------------------------------
    def step(self) -> None:
        g = self.grads[self.steps_done % self.pool]
        self.state, _ = self.step_fn(self.state, g)
        jax.block_until_ready(self.state)
        self.steps_done += 1

    def n_buckets(self) -> int:
        b = math.prod(self.dims)
        return sum(-(-math.prod(s) // b) for s in self.shapes)

    def work_per_step(self) -> W.Work:
        """Project and reconstruct every bucket once."""
        sk = self.assumed["sketch"]
        args = (sk["family"], self.dims, sk["k"], sk["rank"],
                self.n_buckets())
        return W.project_dense(*args) + W.reconstruct_dense(*args)

    def counters(self) -> dict:
        return {"steps": self.steps_done, "buckets": self.n_buckets()}

    def close(self) -> None:
        self.state = self.grads = None

    # -- the comparison -----------------------------------------------------
    def check(self, control=None) -> dict:
        """The numbers compared. `control` is a lower precision, or one of
        `FAULTS`: the reference at that precision, or with that fault,
        then takes the program's place."""
        snap = self.snap
        if control in FAULTS:
            snap = snapshots_of(reference_leaves(
                self.cfg, self.seed, self.pool, ref.HIGHEST, control))
        elif control is not None:
            snap = snapshots_of(reference_leaves(
                self.cfg, self.seed, self.pool, control))
        return compare(snap, reference_leaves(
            self.cfg, self.seed, self.pool, ref.HIGHEST), self.acfg.b1)


@functools.partial(jax.jit, static_argnames=(
    "dims", "k", "rank", "alpha", "adam", "base_key", "precision"))
def _ref_step(p, m, v, e, g, count, *, dims, k, rank, alpha, adam, base_key,
              precision):
    """One reference step on one flattened leaf: sketch and unsketch its
    buckets one by one, error feedback, AdamW."""
    bucket = math.prod(dims)
    size = p.size
    nb = -(-size // bucket)
    per = min(nb, REF_BUCKETS)
    nb = -(-nb // per) * per
    cores = ref.tt_cores(jax.random.fold_in(jax.random.PRNGKey(base_key),
                                            count), dims, k, rank)
    fed = g + e
    x = jnp.concatenate([fed, jnp.zeros(nb * bucket - size, jnp.float32)])
    y = jax.lax.map(lambda xb: ref.tt_project(cores, xb, precision),
                    x.reshape((nb // per, per) + dims))
    xh = jax.lax.map(lambda yb: ref.tt_reconstruct(cores, yb, precision), y)
    g_hat = alpha * xh.reshape(-1)[:size]
    p, m, v = ref.adamw_leaf(p, g_hat, m, v, (count + 1).astype(jnp.float32),
                             *adam)
    return p, m, v, fed - g_hat, g_hat


def _leaf_steps(cfg, key, i, shape, pool, precision, fault=None):
    """The reference's first three steps on leaf i (flattened): g_hat, m and
    the residual after step 1, the parameters after step 3, m0 and p0."""
    a = cfg["assumed"]
    sk = a["sketch"]
    if sk["family"] != "tt":
        raise ValueError("the step reference covers the tt family")
    dims, k, rank = tuple(sk["dims"]), sk["k"], sk["rank"]
    static = dict(dims=dims, k=k, rank=rank,
                  alpha=ref.shrinkage("tt", len(dims), rank, math.prod(dims),
                                      k),
                  adam=(a["lr"], a["b1"], a["b2"], a["eps"],
                        a["weight_decay"]),
                  base_key=a["operator_base_key"], precision=precision)

    def data(kind, slot=0):
        return leaf_data(key, kind, i, shape, a, slot).reshape(-1)

    p0, m0, e0 = data("param"), data("m"), data("resid")
    p, m, v, e = p0, m0, data("v"), e0
    out = {"m0": m0, "p0": p0, "e0": e0}
    for t in range(3):
        g = data("grad", t % pool)
        if fault == "half-batch":
            g = jnp.where(jnp.arange(g.size) < g.size // 2, g, 0.0)
        p, m, v, e, g_hat = _ref_step(
            p, m, v, e, g, jnp.asarray(a["count0"] + t, jnp.int32), **static)
        if t == 0:
            out.update(g1=g_hat, m1=m, e1=e)
    out["p3"] = p
    if fault == "unchanged":
        out.update(m1=m0, e1=e0, p3=p0)
    elif fault == "altered" and i == 0:
        out["p3"] = p.at[0].multiply(1.01)
    return out


FAULTS = ("unchanged", "half-batch", "altered")


def reference_leaves(cfg, seed, pool, precision, fault=None):
    """Yields, leaf by leaf, the reference's readings at `precision`, or
    with one of `FAULTS` planted: the state returned unchanged, half of
    each leaf's gradient left out, one parameter altered by 1%."""
    key = jax_key(seed)
    shapes, _ = _flat_shapes(cfg)
    for i, s in enumerate(shapes):
        yield _leaf_steps(cfg, key, i, s, pool, precision, fault)


def snapshots_of(leaves) -> dict:
    """Turn reference readings into the program's snapshot form (the
    control puts the reference at lower precision in the program's place)."""
    snap = {"m1": [], "e1": [], "p3": []}
    for r in leaves:
        for name in snap:
            snap[name].append(np.asarray(r[name]))
    return snap


def compare(snap: dict, leaves, b1: float) -> dict:
    """The three numbers of the module docstring."""
    gaps = {"grad1": [], "resid1": [], "param3": []}
    norms = {n: [] for n in gaps}
    for i, r in enumerate(leaves):
        m0 = np.asarray(r["m0"], np.float64)
        p0 = np.asarray(r["p0"], np.float64)
        pairs = {
            "grad1": ((np.asarray(snap["m1"][i], np.float64).reshape(-1)
                       - b1 * m0)
                      / (1.0 - b1), r["g1"]),
            "resid1": (np.asarray(snap["e1"][i]).reshape(-1), r["e1"]),
            "param3": (np.asarray(snap["p3"][i], np.float64).reshape(-1)
                       - p0,
                       np.asarray(r["p3"], np.float64) - p0),
        }
        for name, (got, want) in pairs.items():
            got = np.asarray(got, np.float64)
            want = np.asarray(want, np.float64)
            gaps[name].append(float(np.linalg.norm(got - want)))
            norms[name].append(float(np.linalg.norm(want)))
    out = {}
    for name in gaps:
        med = float(np.median(norms[name]))
        out[name] = max(g / max(n, med, 1e-30)
                        for g, n in zip(gaps[name], norms[name]))
    return out


def build(cfg, mix, seed):
    return SketchStep(cfg, mix, seed)
