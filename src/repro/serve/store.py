"""Sketch store + JL similarity retrieval in the compressed domain.

The paper's Thm 1 makes a stored `(k,)` sketch a distance oracle: for any
two inputs, `f(x) - f(y) = f(x - y)` (the map is linear), and
`Var(||f(z)||^2) <= c/k * ||z||^4` with `c` the family's variance factor,
so by Chebyshev

    P( | ||f(x)-f(y)||^2 - ||x-y||^2 | >= eps * ||x-y||^2 ) <= c / (k eps^2)

i.e. with failure probability delta the squared distance between STORED
sketches estimates the true squared distance to relative error
`eps = sqrt(c / (k * delta))` — the distortion bound this store reports
alongside every result. Nearest-neighbor and pairwise-similarity queries
therefore never touch the original (possibly d^N-sized) inputs.

Retrieval is brute-force-but-batched and stays on the device: the stored
rows and their squared norms live in device memory, and each query is ONE
jitted program — a float32 `(B, k) @ (k, capacity)` product over every
stored row, then an exact two-stage top-m (the `top_m` groups of
`query_tile` rows with the smallest minima, then the top m of their rows)
— so a query sends only its own `(B, k)` rows to the chip and copies back
only the `(B, m)` answer. The classic memory/recall-free baseline that JL
embeddings make cheap enough to serve millions of vectors.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core import theory
from repro.rp import ProjectorSpec


@dataclasses.dataclass(frozen=True)
class QueryResult:
    """Top-m retrieval answer with its JL error bar.

    ids   : (B, m) store ids, ascending sketch-space distance.
    dist2 : (B, m) SQUARED sketch-space distances (the JL-estimated
            squared Euclidean distances between the original inputs).
    eps   : relative error of `dist2` as an estimate of the true squared
            distance, each pair holding with failure probability <= delta
            (Thm-1 variance factor + Chebyshev; see module docstring).
    delta : the failure probability `eps` was computed at.
    """

    ids: np.ndarray
    dist2: np.ndarray
    eps: float
    delta: float

    @property
    def dist2_lo(self) -> np.ndarray:
        """Lower end of the per-pair true-squared-distance interval."""
        return self.dist2 / (1.0 + self.eps)

    @property
    def dist2_hi(self) -> np.ndarray:
        """Upper end; +inf when eps >= 1 (k too small for a two-sided bar)."""
        if self.eps >= 1.0:
            return np.full_like(self.dist2, np.inf)
        return self.dist2 / (1.0 - self.eps)


@dataclasses.dataclass(frozen=True)
class PairwiseResult:
    """Pairwise-distance answer (same fields/semantics as QueryResult)."""

    dist2: np.ndarray
    eps: float
    delta: float

    @property
    def dist2_lo(self) -> np.ndarray:
        return self.dist2 / (1.0 + self.eps)

    @property
    def dist2_hi(self) -> np.ndarray:
        if self.eps >= 1.0:
            return np.full_like(self.dist2, np.inf)
        return self.dist2 / (1.0 - self.eps)


def _sq_norms(x):
    """Row-wise squared norms, summed in float32 on the device."""
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=-1)


@functools.partial(jax.jit, static_argnums=2)
def _grown(data, norms2, cap):
    """The store's buffers copied into room for `cap` rows (zero-padded)."""
    pad = cap - data.shape[0]
    return jnp.pad(data, ((0, pad), (0, 0))), jnp.pad(norms2, (0, pad))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _append(data, norms2, n, rows):
    """Rows written at `n` in place (the buffers are donated), with their
    squared norms; returns the buffers and the new row count."""
    data = lax.dynamic_update_slice(data, rows, (n, 0))
    norms2 = lax.dynamic_update_slice(norms2, _sq_norms(rows), (n,))
    return data, norms2, n + rows.shape[0]


@functools.partial(jax.jit, static_argnames=("top_m", "tile"))
def _nearest(data, norms2, n, q, *, top_m, tile):
    """Exact top-m of `||q||^2 - 2 q.x + ||x||^2` over the first `n` rows.

    Stage 1 takes the `top_m` groups of `tile` consecutive rows whose
    smallest distance is least; stage 2 the top m of those groups' rows.
    Exact: each chosen group holds a distance no larger than any unchosen
    group's minimum, so the m least distances all lie in the chosen
    groups. Rows at or past `n` read +inf and are never chosen while
    `top_m <= n`."""
    b, cap = q.shape[0], data.shape[0]
    dots = jnp.einsum("bk,nk->bn", q, data,
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    d2 = _sq_norms(q)[:, None] - 2.0 * dots + norms2[None]
    d2 = jnp.where(jnp.arange(cap) < n, d2, jnp.inf)
    groups = -(-cap // tile)
    d2 = jnp.pad(d2, ((0, 0), (0, groups * tile - cap)),
                 constant_values=jnp.inf).reshape(b, groups, tile)
    _, gids = lax.top_k(-jnp.min(d2, axis=2), min(top_m, groups))
    cand = jnp.take_along_axis(d2, gids[:, :, None], axis=1).reshape(b, -1)
    neg, pos = lax.top_k(-cand, top_m)
    ids = jnp.take_along_axis(gids, pos // tile, axis=1) * tile + pos % tile
    return ids, jnp.maximum(-neg, 0.0)


@jax.jit
def _pair_dist2(data, ids_a, ids_b):
    diff = (data[ids_a].astype(jnp.float32)
            - data[ids_b].astype(jnp.float32))
    return jnp.sum(diff * diff, axis=-1)


class SketchStore:
    """Append-only store of `(k,)` sketches from ONE projector spec.

    One spec per store, on purpose: sketches from different operators live
    in unrelated embeddings and their mutual distances are meaningless —
    the serving engine keys ingestion on the store's spec. Rows and their
    squared norms are held in device arrays that grow by doubling from
    1024 rows (each growth one copy of the store in device memory, counted
    by `store/grows`); `add` writes into them in place.
    """

    def __init__(self, spec: ProjectorSpec, *, query_tile: int = 4096,
                 delta: float = 0.01):
        if query_tile < 1:
            raise ValueError(f"query_tile must be >= 1, got {query_tile}")
        if not 0.0 < delta < 1.0:
            raise ValueError(f"delta must be in (0, 1), got {delta}")
        self.spec = spec
        self.k = spec.k
        self.query_tile = int(query_tile)
        self.delta = float(delta)
        # Thm-1 variance factor of the spec's family at its order/rank —
        # the c in eps = sqrt(c / (k delta)).
        self.var_factor = theory.variance_factor(
            spec.family, N=len(spec.dims), R=spec.rank, D=spec.input_size)
        # device buffers, allocated by the first ingest (which fixes the
        # dtype); `_count` is the row count kept on the device, so neither
        # `add` nor `query` sends it
        self._data = None
        self._norms2 = None
        self._count = None
        self._n = 0
        self._dtype: np.dtype | None = None

    def __len__(self) -> int:
        return self._n

    def nbytes(self) -> int:
        """Resident sketch bytes (the 'millions of users' memory axis)."""
        if self._data is None:
            return 0
        return self._n * self.k * self._data.dtype.itemsize

    def eps_bound(self, delta: float | None = None) -> float:
        """Thm-1/Chebyshev relative error of squared distances at `delta`."""
        delta = self.delta if delta is None else delta
        if not 0.0 < delta < 1.0:
            raise ValueError(f"delta must be in (0, 1), got {delta}")
        return math.sqrt(self.var_factor / (self.k * delta))

    # -- ingest ----------------------------------------------------------
    def add(self, sketches) -> np.ndarray:
        """Append `(B, k)` (or a single `(k,)`) sketches; returns their ids.

        `sketches` is a host array or a device array; a device array is
        written on the device and never copied through the host.

        The store's element dtype is fixed by the FIRST ingest; mixing
        dtypes afterwards is a typed error — silently upcasting would make
        stored distances incomparable across rows (and hide a producer
        regression), exactly the misuse the serve config errors guard.
        """
        on_device = isinstance(sketches, jax.Array)
        arr = sketches if on_device else np.asarray(sketches)
        if arr.ndim == 1:
            arr = arr[None]
        if arr.ndim != 2 or arr.shape[1] != self.k:
            raise ValueError(
                f"sketches of shape {np.shape(sketches)} do not end in the "
                f"store's k = {self.k}")
        dt = np.dtype(arr.dtype)
        if self._dtype is None:
            self._dtype = dt
        elif dt != self._dtype:
            raise ValueError(
                f"mixed-dtype ingest: store holds {self._dtype.name} "
                f"sketches, got {dt.name}; re-sketch with a consistent "
                "dtype (one spec, one dtype per store)")
        b = arr.shape[0]
        cap = 0 if self._data is None else self._data.shape[0]
        if self._n + b > cap:
            # room first, the upload after it: the peak is then the old and
            # the new buffers, not those and the new rows besides
            grown = max(2 * cap, self._n + b, 1024)
            if self._data is None:
                self._data = jnp.zeros((grown, self.k),
                                       jax.dtypes.canonicalize_dtype(dt))
                self._norms2 = jnp.zeros((grown,), jnp.float32)
                self._count = jnp.zeros((), jnp.int32)
            else:
                obs.counter("store/grows").inc()
                self._data, self._norms2 = _grown(self._data, self._norms2,
                                                  grown)
        if not on_device:
            arr = jnp.asarray(arr)
            obs.counter("store/h2d_bytes").inc(arr.nbytes)
        self._data, self._norms2, self._count = _append(
            self._data, self._norms2, self._count, arr)
        ids = np.arange(self._n, self._n + b)
        self._n += b
        return ids

    def _ids(self, ids) -> np.ndarray:
        ids = np.asarray(ids)
        if ids.size and (ids.min() < 0 or ids.max() >= self._n):
            raise ValueError(f"sketch id out of range [0, {self._n})")
        obs.counter("store/h2d_bytes").inc(ids.nbytes)
        return ids

    def get(self, ids) -> np.ndarray:
        """Stored sketches by id, gathered on the device (a host copy)."""
        return np.asarray(self._data[self._ids(ids)])

    # -- retrieval -------------------------------------------------------
    def query(self, q, top_m: int, *, delta: float | None = None
              ) -> QueryResult:
        """Top-m nearest stored sketches for each query row.

        q     : one `(k,)` sketch or a `(B, k)` stack of them.
        top_m : results per query; must satisfy 1 <= top_m <= len(store)
                (a typed error otherwise — asking for more neighbors than
                the store holds is a caller bug, not a clamp).

        One program per (B, capacity, top_m, query_tile, dtype): an `add`
        that does not grow the store reuses it.
        """
        if self._n == 0:
            raise ValueError("query on an empty store; ingest sketches "
                             "first")
        if not 1 <= top_m <= self._n:
            raise ValueError(
                f"top_m={top_m} out of range: store holds {self._n} "
                f"sketches (need 1 <= top_m <= {self._n})")
        q = np.asarray(q)
        squeeze = q.ndim == 1
        if squeeze:
            q = q[None]
        if q.ndim != 2 or q.shape[1] != self.k:
            raise ValueError(f"query of shape {q.shape} does not end in the "
                             f"store's k = {self.k}")
        q = q.astype(self._dtype, copy=False)
        with obs.span("store.query", rows=self._n,
                      tiles=-(-self._n // self.query_tile), batch=q.shape[0],
                      top_m=top_m):
            with obs.span("store.sweep"):
                out = _nearest(self._data, self._norms2, self._count, q,
                               top_m=top_m, tile=self.query_tile)
            with obs.span("store.fetch"):
                best_i, best_d = jax.device_get(out)
            # the bytes this call sent to the device: the queries alone
            obs.counter("store/h2d_bytes").inc(q.nbytes)
        best_i = best_i.astype(np.int64)
        if squeeze:
            best_d, best_i = best_d[0], best_i[0]
        delta = self.delta if delta is None else delta
        return QueryResult(ids=best_i, dist2=best_d,
                           eps=self.eps_bound(delta), delta=delta)

    def pairwise(self, ids_a, ids_b, *, delta: float | None = None
                 ) -> PairwiseResult:
        """Squared distances between stored sketch pairs, with error bars.

        ids_a / ids_b broadcast elementwise; each reported `dist2[i]`
        estimates the true squared distance of the ORIGINAL inputs to
        relative error `eps` (per pair, failure probability <= delta).
        """
        d2 = np.asarray(_pair_dist2(self._data, self._ids(ids_a),
                                    self._ids(ids_b)))
        delta = self.delta if delta is None else delta
        return PairwiseResult(dist2=d2, eps=self.eps_bound(delta),
                              delta=delta)
