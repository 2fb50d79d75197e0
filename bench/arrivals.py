"""The one traffic generator: a traffic file's parameters and a seed in, the
window's requests out.

A traffic file (`bench/traffic/<mix>.json`) is data only. Its keys:

* `system`: the adapter that drives the program, `bench/systems/<system>.py`;
* `loop`: `closed` (the next call when the last returns) or `open` (requests
  offered at their due times, whatever the system does);
* `arrivals` (open loop): `{"process": <name>, ...}`, the arrival process
  `bench/processes/<name>.py` and its parameters;
* `requests` (closed loop with a mix): how many requests to make; the loop
  cycles through them;
* `mix`: a list of request kinds, each `{"op": <name>, "weight": w, ...}`
  with any of
    - `params`: fixed parameters, `{"top_m": 10}`;
    - `cycle`: lists cycled within the kind, `{"rank": [2, 3, 4]}`;
    - `spread`: integers `[lo, hi]` spaced evenly over the range, one for
      each request of the kind, in a seeded order, `{"length": [a, b]}`;
    - `pick`: an item of the system's store, drawn by popularity,
      `{"item": {"zipf": 0.99}}` (Zipf over a seeded permutation; 0.99 is
      YCSB's zipfian constant);
* `params`: parameters of the system itself, `{"grad_pool": 3}`.

Each kind gets its exact share of the requests by weight and the seed
shuffles which arrival gets which; cycled and spread values give every
seed the same set of sizes, in another order. The op names and parameters
mean what the adapter makes of them; it refuses ops it does not serve.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

TRAFFIC_KEYS = {"system", "loop", "arrivals", "requests", "mix", "params"}
ENTRY_KEYS = {"op", "weight", "params", "cycle", "spread", "pick"}


@dataclasses.dataclass
class Request:
    """One arrival. `due` is seconds after the window opened."""

    idx: int
    due: float
    op: str
    params: dict


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """A numpy generator for (seed, tags); any non-negative seed."""
    return np.random.default_rng([int(seed), *map(int, tags)])


def jax_key(seed: int):
    """A JAX PRNG key from any non-negative seed, also one past 32 bits."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def process(name: str):
    """The arrival process `bench/processes/<name>.py`."""
    return importlib.import_module(f"bench.processes.{name}")


def validate(mix: dict) -> None:
    extra = set(mix) - TRAFFIC_KEYS
    if extra:
        raise ValueError(f"unknown traffic keys {sorted(extra)}")
    if "system" not in mix:
        raise ValueError("a traffic file names its system")
    loop = mix.get("loop")
    if loop not in ("open", "closed"):
        raise ValueError(f"traffic loop must be 'open' or 'closed', "
                         f"got {loop!r}")
    if (loop == "open") != ("arrivals" in mix):
        raise ValueError("an open loop, and only an open loop, has arrivals")
    if loop == "open" and "mix" not in mix:
        raise ValueError("an open loop needs a mix")
    for e in mix.get("mix", []):
        extra = set(e) - ENTRY_KEYS
        if extra:
            raise ValueError(f"unknown mix-entry keys {sorted(extra)}")
        if not e["weight"] > 0:
            raise ValueError("mix weights must be > 0")
        for name, (lo, hi) in e.get("spread", {}).items():
            if not int(lo) <= int(hi):
                raise ValueError(f"spread {name!r} needs lo <= hi")


def _shares(weights, n):
    """Largest-remainder split of n by weights."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out


def _zipf_cdf(n_items: int, s: float) -> np.ndarray:
    p = np.arange(1, n_items + 1, dtype=np.float64) ** -s
    return np.cumsum(p / p.sum())


def requests(mix: dict, seconds: float, seed: int, *,
             n_items: int | None = None) -> list[Request] | None:
    """The requests of one window, sorted by due time (all due at once in a
    closed loop); None for a closed loop that sends no requests."""
    validate(mix)
    if "mix" not in mix:
        return None
    rng = rng_for(seed, 1)
    if mix["loop"] == "open":
        arr = dict(mix["arrivals"])
        due = np.sort(np.asarray(process(arr.pop("process")).times(
            arr, seconds, rng_for(seed, 6)), np.float64))
        n = len(due)
    else:
        n = int(mix["requests"])
        due = np.zeros(n)
    entries = mix["mix"]
    counts = _shares([e["weight"] for e in entries], n)
    kinds = np.repeat(np.arange(len(entries)), counts)
    rng.shuffle(kinds)
    spread = {}
    for j, e in enumerate(entries):
        for t, (name, (lo, hi)) in enumerate(sorted(e.get("spread",
                                                          {}).items())):
            vals = np.rint(np.linspace(int(lo), int(hi),
                                       max(int(counts[j]), 1))).astype(int)
            spread[j, name] = rng_for(seed, 7, j, t).permutation(vals)
    picks = {}
    seen = np.zeros(len(entries), np.int64)
    out = []
    for i in range(n):
        j = int(kinds[i])
        e = entries[j]
        c = int(seen[j])
        seen[j] += 1
        p = dict(e.get("params", {}))
        for name, vals in e.get("cycle", {}).items():
            p[name] = vals[c % len(vals)]
        for name in e.get("spread", {}):
            p[name] = int(spread[j, name][c])
        for t, (name, how) in enumerate(sorted(e.get("pick", {}).items())):
            if n_items is None:
                raise ValueError("picking an item needs the store size")
            if (j, name) not in picks:
                picks[j, name] = (_zipf_cdf(n_items, float(how["zipf"])),
                                  rng_for(seed, 2, j, t).permutation(n_items))
            cdf, perm = picks[j, name]
            r = min(int(np.searchsorted(cdf, rng.uniform())), n_items - 1)
            p[name] = int(perm[r])
        out.append(Request(idx=i, due=float(due[i]), op=e["op"], params=p))
    return out
