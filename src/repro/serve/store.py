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

Retrieval is brute-force-but-batched: one `(B, k) @ (k, tile)` matmul per
tile of the store sweeps all n stored sketches (`query_tile` rows at a
time, bounding the distance intermediate), with a running top-m merge on
the host between tiles — the classic memory/recall-free baseline that JL
embeddings make cheap enough to serve millions of vectors.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

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


class SketchStore:
    """Append-only store of `(k,)` sketches from ONE projector spec.

    One spec per store, on purpose: sketches from different operators live
    in unrelated embeddings and their mutual distances are meaningless —
    the serving engine keys ingestion on the store's spec. Rows are held in
    a growable (doubling) host array; matmul tiles move to the accelerator
    per sweep step.
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
        self._data = np.empty((0, self.k), np.float32)
        self._norms2 = np.empty((0,), np.float32)
        self._n = 0
        self._dtype: np.dtype | None = None

    def __len__(self) -> int:
        return self._n

    def nbytes(self) -> int:
        """Resident sketch bytes (the 'millions of users' memory axis)."""
        return self._n * self.k * self._data.itemsize

    def eps_bound(self, delta: float | None = None) -> float:
        """Thm-1/Chebyshev relative error of squared distances at `delta`."""
        delta = self.delta if delta is None else delta
        if not 0.0 < delta < 1.0:
            raise ValueError(f"delta must be in (0, 1), got {delta}")
        return math.sqrt(self.var_factor / (self.k * delta))

    # -- ingest ----------------------------------------------------------
    def add(self, sketches) -> np.ndarray:
        """Append `(B, k)` (or a single `(k,)`) sketches; returns their ids.

        The store's element dtype is fixed by the FIRST ingest; mixing
        dtypes afterwards is a typed error — silently upcasting would make
        stored distances incomparable across rows (and hide a producer
        regression), exactly the misuse the serve config errors guard.
        """
        arr = np.asarray(sketches)
        if arr.ndim == 1:
            arr = arr[None]
        if arr.ndim != 2 or arr.shape[1] != self.k:
            raise ValueError(
                f"sketches of shape {np.shape(sketches)} do not end in the "
                f"store's k = {self.k}")
        dt = np.dtype(arr.dtype)
        if self._dtype is None:
            self._dtype = dt
            self._data = self._data.astype(dt)
        elif dt != self._dtype:
            raise ValueError(
                f"mixed-dtype ingest: store holds {self._dtype.name} "
                f"sketches, got {dt.name}; re-sketch with a consistent "
                "dtype (one spec, one dtype per store)")
        b = arr.shape[0]
        if self._n + b > self._data.shape[0]:
            cap = max(2 * self._data.shape[0], self._n + b, 1024)
            grown = np.empty((cap, self.k), self._dtype)
            grown[:self._n] = self._data[:self._n]
            self._data = grown
            grown_n = np.empty((cap,), np.float32)
            grown_n[:self._n] = self._norms2[:self._n]
            self._norms2 = grown_n
        ids = np.arange(self._n, self._n + b)
        self._data[self._n:self._n + b] = arr
        self._norms2[self._n:self._n + b] = np.einsum(
            "bk,bk->b", arr, arr, dtype=np.float32)
        self._n += b
        return ids

    def get(self, ids) -> np.ndarray:
        """Stored sketches by id (view into the store)."""
        return self._data[:self._n][np.asarray(ids)]

    # -- retrieval -------------------------------------------------------
    def query(self, q, top_m: int, *, delta: float | None = None
              ) -> QueryResult:
        """Top-m nearest stored sketches for each query row.

        q     : one `(k,)` sketch or a `(B, k)` stack of them.
        top_m : results per query; must satisfy 1 <= top_m <= len(store)
                (a typed error otherwise — asking for more neighbors than
                the store holds is a caller bug, not a clamp).
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
        nb = q.shape[0]
        with obs.span("store.query", rows=self._n,
                      tiles=-(-self._n // self.query_tile), batch=nb,
                      top_m=top_m):
            qj = jnp.asarray(q)
            qn = np.einsum("bk,bk->b", q, q, dtype=np.float32)
            best_d = np.full((nb, top_m), np.inf, np.float32)
            best_i = np.full((nb, top_m), -1, np.int64)
            for start in range(0, self._n, self.query_tile):
                stop = min(start + self.query_tile, self._n)
                tile = self._data[start:stop]
                # ONE matmul per tile: (B, k) @ (k, tile) on the accelerator.
                with obs.span("store.upload"):
                    up = jnp.asarray(tile.T)
                with obs.span("store.fetch"):
                    dots = np.asarray(jnp.matmul(qj, up), np.float32)
                with obs.span("store.merge"):
                    d2 = (qn[:, None] - 2.0 * dots
                          + self._norms2[start:stop][None])
                    cand_d = np.concatenate([best_d, d2], axis=1)
                    cand_i = np.concatenate(
                        [best_i, np.broadcast_to(np.arange(start, stop),
                                                 (nb, stop - start))],
                        axis=1)
                    keep = np.argpartition(cand_d, top_m - 1,
                                           axis=1)[:, :top_m]
                    best_d = np.take_along_axis(cand_d, keep, axis=1)
                    best_i = np.take_along_axis(cand_i, keep, axis=1)
            # the bytes this call sent to the device: the queries and every
            # stored row, once
            obs.counter("store/h2d_bytes").inc(
                q.nbytes + self._n * self.k * self._data.itemsize)
            order = np.argsort(best_d, axis=1, kind="stable")
            best_d = np.maximum(np.take_along_axis(best_d, order, axis=1),
                                0.0)
            best_i = np.take_along_axis(best_i, order, axis=1)
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
        ids_a = np.asarray(ids_a)
        ids_b = np.asarray(ids_b)
        for ids in (ids_a, ids_b):
            if ids.size and (ids.min() < 0 or ids.max() >= self._n):
                raise ValueError(f"sketch id out of range [0, {self._n})")
        diff = (self._data[:self._n][ids_a].astype(np.float32)
                - self._data[:self._n][ids_b].astype(np.float32))
        d2 = np.einsum("...k,...k->...", diff, diff)
        delta = self.delta if delta is None else delta
        return PairwiseResult(dist2=d2, eps=self.eps_bound(delta),
                              delta=delta)
