"""Plain float32 references, independent of the program under test.

Nothing here imports the program. The operators are drawn from their PRNG
keys by the published definitions (Rakhshan & Rabusseau 2020, Definitions
1 and 2), with the same key schedule the system documents for
(spec, seed): `split(key, N)`, one normal draw per core. Every contraction
is an einsum at an explicit precision: "highest" (float32) for the
reference, and for the control that the comparison has to fail the step
below it, written out so that it computes the same on any backend:
"high" is three bfloat16 passes (hi*hi + hi*lo + lo*hi, as the MXU's
`Precision.HIGH`), "bf16" one pass.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
PRECISIONS = ("highest", "high", "bf16")


def _bf16(a):
    """`a` rounded to bfloat16, kept in float32. `reduce_precision` is never
    dropped as excess precision, as a round trip through a bfloat16 convert
    may be on a TPU."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def einsum(spec, a, b, precision=HIGHEST):
    """A two-operand float32 einsum at `precision` (see the module doc)."""
    exact = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=exact)
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return (jnp.einsum(spec, ah, bh, precision=exact)
                + jnp.einsum(spec, ah, bl, precision=exact)
                + jnp.einsum(spec, al, bh, precision=exact))
    if precision == "bf16":
        return jnp.einsum(spec, _split(a)[0], _split(b)[0], precision=exact)
    raise ValueError(f"precision must be one of {PRECISIONS}, got "
                     f"{precision!r}")


# -- operators ---------------------------------------------------------------

def tt_cores(key, dims, k, rank):
    """f_TT(R) cores, `(k, r_{n-1}, d_n, r_n)`: variance 1/sqrt(R) at the two
    boundary cores and 1/R inside (Definition 1)."""
    n_modes = len(dims)
    ranks = [1] + [rank] * (n_modes - 1) + [1]
    keys = jax.random.split(key, n_modes)
    cores = []
    for n in range(n_modes):
        if n_modes == 1:
            var = 1.0
        elif n in (0, n_modes - 1):
            var = 1.0 / jnp.sqrt(jnp.asarray(rank, jnp.float32))
        else:
            var = 1.0 / rank
        std = jnp.sqrt(jnp.asarray(var, jnp.float32))
        cores.append(std * jax.random.normal(
            keys[n], (k, ranks[n], dims[n], ranks[n + 1]), jnp.float32))
    return tuple(cores)


def cp_factors(key, dims, k, rank):
    """f_CP(R) factors, `(k, d_n, R)`: variance (1/R)^(1/N) (Definition 2)."""
    n_modes = len(dims)
    std = jnp.asarray((1.0 / rank) ** (1.0 / (2.0 * n_modes)), jnp.float32)
    keys = jax.random.split(key, n_modes)
    return tuple(std * jax.random.normal(keys[n], (k, dims[n], rank),
                                         jnp.float32)
                 for n in range(n_modes))


def variance_factor(family, order, rank):
    """Theorem 1: Var ||f(X)||^2 <= c/k ||X||^4."""
    if family == "tt":
        return 3.0 * (1.0 + 2.0 / rank) ** (order - 1) - 1.0
    if family == "cp":
        return 3.0 ** (order - 1) * (1.0 + 2.0 / rank) - 1.0
    raise ValueError(f"no reference for family {family!r}")


def shrinkage(family, order, rank, bucket_elems, k):
    """MMSE damping of the adjoint round trip, 1 / (1 + c D / k)."""
    return 1.0 / (1.0 + variance_factor(family, order, rank)
                  * bucket_elems / k)


# -- dense projection and its adjoint ----------------------------------------

def _letters(n):
    return "abcdefgh"[:n]


def tt_project(cores, x, precision=HIGHEST):
    """(B, *dims) -> (B, k): y_i = <TT row i, X> / sqrt(k)."""
    k = cores[0].shape[0]
    n = len(cores)
    idx = _letters(n)
    # right to left, carrying (B, d_1..d_m, k, r_m)
    c = einsum(f"z{idx},kr{idx[-1]}->z{idx[:-1]}kr", x,
               cores[-1][..., 0], precision)
    for m in range(n - 2, 0, -1):
        c = einsum(f"z{idx[:m + 1]}kr,ks{idx[m]}r->z{idx[:m]}ks", c,
                   cores[m], precision)
    y = einsum(f"z{idx[0]}kr,k{idx[0]}r->zk", c, cores[0][:, 0],
               precision)
    return y / math.sqrt(k)


def tt_reconstruct(cores, y, precision=HIGHEST):
    """(B, k) -> (B, *dims): sum_i y_i S_i / sqrt(k)."""
    k = cores[0].shape[0]
    n = len(cores)
    idx = _letters(n)
    w = einsum(f"zk,k{idx[0]}r->zk{idx[0]}r", y, cores[0][:, 0],
               precision)
    for m in range(1, n - 1):
        w = einsum(f"zk{idx[:m]}r,kr{idx[m]}s->zk{idx[:m + 1]}s", w,
                   cores[m], precision)
    x = einsum(f"zk{idx[:n - 1]}r,kr{idx[n - 1]}->z{idx}", w,
               cores[-1][..., 0], precision)
    return x / math.sqrt(k)


def cp_project(factors, x, precision=HIGHEST):
    """(B, *dims) -> (B, k) for f_CP(R)."""
    k = factors[0].shape[0]
    n = len(factors)
    idx = _letters(n)
    c = einsum(f"z{idx},k{idx[-1]}r->z{idx[:-1]}kr", x, factors[-1],
               precision)
    for m in range(n - 2, -1, -1):
        c = einsum(f"z{idx[:m + 1]}kr,k{idx[m]}r->z{idx[:m]}kr", c,
                   factors[m], precision)
    return c.sum(-1) / math.sqrt(k)


# -- structured payloads, densified ------------------------------------------

def tt_full(cores):
    """A TT tensor, cores `(r_{n-1}, d_n, r_n)`, as a dense array."""
    out = np.asarray(cores[0], np.float64)[0]            # (d1, r1)
    for c in cores[1:]:
        out = np.tensordot(out, np.asarray(c, np.float64), axes=([-1], [0]))
    return out[..., 0]


def cp_full(factors):
    """A CP tensor, factors `(d_n, R)` and unit weights, as a dense array."""
    n = len(factors)
    idx = _letters(n)
    return np.einsum(",".join(f"{i}r" for i in idx) + f"->{idx}",
                     *[np.asarray(f, np.float64) for f in factors])


# -- AdamW -------------------------------------------------------------------

def adamw_leaf(p, g, m, v, count, lr, b1, b2, eps, wd):
    """One decoupled-weight-decay Adam step on one leaf; `count` is the step
    number after the increment."""
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
    return p, m, v
