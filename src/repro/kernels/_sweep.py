"""Pallas machinery for the order-N mode-sweep kernels (TT and CP).

The kernel bodies execute the program emitted by the contraction planner
(`ops.plan_contraction`) verbatim — `steps` arrives as a static tuple, so
each (family, kind, order, tiling) compiles exactly once. Nothing here is
family-specific beyond what the program encodes: a TT transfer core is a
"full" bond coupling, a CP factor a "diag" one.

Every step is an operation Mosaic lowers directly, with the sketch row
axis k on the 128 lanes: a 2-D MXU matmul with one contracting dimension
("dot"), or a broadcast-multiply followed by a sublane sum ("reduce") or
preceded by nothing but a leading-dim collapse ("expand", the outer
product of the adjoint). Values are lists of `(rows, TK)` bond slabs.

Grid conventions:
* project: grid = (k/TK, B/TB, d1/BA), k-tile OUTERMOST; the (TB, TK)
  output block accumulates over the d1 axis.
* reconstruct: grid = (B/TB, d1/BA, k/TK), k-tile INNERMOST; the
  (TB, BA*Q, L) output block accumulates over k.

`sweep_project_pipelined` is the DOUBLE-BUFFERED variant of the project
schedule (plan `pipeline='double'`): the d1 grid axis moves inside the
kernel as a fori_loop and the two streamed operands — the input block and
the d1-tiled leading core — are prefetched into a second VMEM slot with
explicit `pltpu.make_async_copy` DMAs while the current tile contracts.
The other cores keep their BlockSpec residency (their index depends only
on ik). Analytic HBM traffic is IDENTICAL to the serial schedule
(`ops.sweep_hbm_bytes`): pipelining buys overlap, not fewer bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import HIGHEST, VMEM_LIMIT_BYTES


def compiler_params():
    """Mosaic compiler parameters shared by every kernel of the repo: the
    scoped-VMEM limit the planners budget against."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def dot(a, b):
    """The one MXU step: a 2-D matmul at full float32 precision."""
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _reduce(z, load, step):
    """("reduce", coupling, n_dst, n_src, d): z_j' = sum_i sum_d z_i * W[j, i]
    over the d sublanes; `load(j, i)` gives the `(d, TK)` core slice."""
    _, coupling, n_dst, _, _ = step
    w0 = load(0, 0)
    d, tk = w0.shape
    if d == 1:                           # a one-row leading tile: no sum
        def mul(zi, w):
            return zi * w

        def red(a):
            return a
    else:
        z = [zi.reshape(-1, d, tk) for zi in z]

        def mul(zi, w):
            return zi * w[None]

        def red(a):
            return jnp.sum(a, axis=1)
    if coupling == "diag":
        return [red(mul(zi, load(i, 0))) for i, zi in enumerate(z)]
    out = []
    for j in range(n_dst):
        acc = mul(z[0], w0 if j == 0 else load(j, 0))
        for i in range(1, len(z)):
            acc = acc + mul(z[i], load(j, i))
        out.append(red(acc))
    return out


def _expand(z, load, step):
    """("expand", coupling, n_dst, n_src, d), the adjoint of `_reduce`:
    w_i' = sum_j w_j (x) W[j, i], an outer product over the d sublanes."""
    _, coupling, _, n_src, _ = step
    tk = z[0].shape[-1]

    def outer(zi, w):
        if w.shape[0] == 1:              # a one-row leading tile
            return zi * w
        return zi[:, None, :] * w[None]

    def flat(a):
        return a.reshape(-1, tk) if a.ndim == 3 else a

    if coupling == "diag":
        return [flat(outer(zi, load(i, 0))) for i, zi in enumerate(z)]
    out = []
    for i in range(n_src):
        acc = outer(z[0], load(0, i))
        for j in range(1, len(z)):
            acc = acc + outer(z[j], load(j, i))
        out.append(flat(acc))
    return out


def _slices(ref):
    """`load(j, i)` over a `(n_dst, n_src, d, TK)` core ref."""
    return lambda j, i: ref[j, i]


def _lead_slices(ref):
    """`load(0, i)` over the `(BA, R, TK)` leading-core ref: a strided
    `(BA, TK)` load."""
    return lambda j, i: ref[:, i, :]


def project_body(x, loads, steps):
    """The projection program on one `(TB, BA*Q, L)` input block ->
    `(TB, TK)`. `loads[0](j)` is the dot weight of bond j, `loads[s]` the
    slice loader of step s."""
    x2 = x.reshape(-1, x.shape[-1])
    z = [dot(x2, loads[0](j)) for j in range(steps[0][1])]
    for step, load in zip(steps[1:], loads[1:]):
        z = _reduce(z, load, step)
    return z[0]


def reconstruct_body(y, loads, steps):
    """The adjoint program on one `(TB, TK)` sketch block ->
    `(TB*BA*Q, L)`; `loads[-1](j)` is the dot weight of bond j."""
    z = [y]
    for step, load in zip(steps[:-1], loads[:-1]):
        z = _expand(z, load, step)
    out = dot(z[0], loads[-1](0))
    for j in range(1, steps[-1][1]):
        out = out + dot(z[j], loads[-1](j))
    return out


def _project_kernel(x_ref, *refs, steps, scale):
    w_refs, o_ref = refs[:-1], refs[-1]
    ia = pl.program_id(2)
    loads = ([lambda j, r=w_refs[0]: r[j]]
             + [_slices(r) for r in w_refs[1:-1]] + [_lead_slices(w_refs[-1])])
    y = project_body(x_ref[...], loads, steps) * scale

    @pl.when(ia == 0)
    def _init():
        o_ref[...] = y

    @pl.when(ia != 0)
    def _acc():
        o_ref[...] += y


def reconstruct_loads(w_refs):
    return ([_lead_slices(w_refs[0])] + [_slices(r) for r in w_refs[1:-1]]
            + [lambda j, r=w_refs[-1]: r[j]])


def _reconstruct_kernel(y_ref, *refs, steps, scale):
    w_refs, o_ref = refs[:-1], refs[-1]
    ik = pl.program_id(2)
    out = reconstruct_body(y_ref[...], reconstruct_loads(w_refs), steps)
    out = out.reshape(o_ref.shape) * scale

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = out

    @pl.when(ik != 0)
    def _acc():
        o_ref[...] += out


def _imap(*pattern):
    """Index map selecting grid axes by position (`int`) or pinning 0
    (`None`)."""
    def f(*prog):
        return tuple(prog[p] if p is not None else 0 for p in pattern)
    return f


def core_specs(weights, kind, *, tk, ba, k_pos, lead_pos):
    """BlockSpecs for the step operands (see `ops.sweep_operands`): the dot
    weight and the interior cores are whole per k-tile (grid axis `k_pos`),
    so they stay VMEM-resident across it; the leading core is also tiled on
    its mode axis by the d1 grid axis at `lead_pos` (None: not passed)."""
    dot_at = 0 if kind == "project" else len(weights) - 1
    lead_at = len(weights) - 1 if kind == "project" else 0
    specs = []
    for n, w in enumerate(weights):
        if n == dot_at and kind == "project":
            blk, idx = (w.shape[0], w.shape[1], tk), (None, None, k_pos)
        elif n == dot_at:
            blk, idx = (w.shape[0], tk, w.shape[2]), (None, k_pos, None)
        elif n == lead_at and lead_pos is not None:
            blk, idx = (ba, w.shape[1], tk), (lead_pos, None, k_pos)
        else:
            blk, idx = w.shape[:3] + (tk,), (None, None, None, k_pos)
        specs.append(pl.BlockSpec(blk, _imap(*idx)))
    return specs


@functools.partial(jax.jit, static_argnames=("steps", "tk", "tb", "ba",
                                             "scale", "interpret"))
def sweep_project(x: jnp.ndarray, *weights: jnp.ndarray, steps, tk: int,
                  tb: int, ba: int, scale: float,
                  interpret: bool) -> jnp.ndarray:
    """x (B, d1*Q, L) float32, weights per `ops.sweep_operands`; requires
    K % tk == 0, B % tb == 0, d1 % ba == 0. Returns (B, K) float32."""
    b, rows, ell = x.shape
    k = weights[0].shape[-1]
    d1 = weights[-1].shape[0]
    q = rows // d1
    assert k % tk == 0 and b % tb == 0 and d1 % ba == 0, (k, tk, b, tb, d1, ba)
    in_specs = [pl.BlockSpec((tb, ba * q, ell), _imap(1, 2, None))]
    in_specs += core_specs(weights, "project", tk=tk, ba=ba, k_pos=0,
                           lead_pos=2)
    return pl.pallas_call(
        functools.partial(_project_kernel, steps=steps, scale=scale),
        name="sweep_project",
        grid=(k // tk, b // tb, d1 // ba),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, tk), _imap(1, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(x, *weights)


def _project_pipelined_kernel(x_hbm, *refs, steps, scale, na, tk, tb, ba,
                              q):
    w_refs, c0_hbm, o_ref = refs[:-2], refs[-2], refs[-1]
    ik = pl.program_id(0)
    ib = pl.program_id(1)
    n_lead = c0_hbm.shape[1]

    def body(xs, cs, sems):
        # slot s of xs/cs holds d1-tile i with s == i % 2; sems[0] guards
        # the input-block copies, sems[1] the leading-core copies
        def x_dma(slot, i):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(ib * tb, tb), pl.ds(i * ba * q, ba * q)],
                xs.at[slot], sems.at[0, slot])

        def c_dma(slot, i):
            return pltpu.make_async_copy(
                c0_hbm.at[pl.ds(i * ba, ba), pl.ds(0, n_lead),
                          pl.ds(ik * tk, tk)],
                cs.at[slot], sems.at[1, slot])

        x_dma(0, 0).start()              # warm-up: tile 0 into slot 0
        c_dma(0, 0).start()

        def step(i, acc):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < na)
            def _prefetch():             # next tile streams during compute
                x_dma(nxt, i + 1).start()
                c_dma(nxt, i + 1).start()

            x_dma(slot, i).wait()
            c_dma(slot, i).wait()
            loads = ([lambda j: w_refs[0][j]]
                     + [_slices(r) for r in w_refs[1:]]
                     + [lambda j, i_: cs[slot, :, i_, :]])
            return acc + project_body(xs[slot], loads, steps)

        acc = jax.lax.fori_loop(0, na, step,
                                jnp.zeros((tb, tk), jnp.float32))
        o_ref[...] = acc * scale

    ell = x_hbm.shape[-1]
    pl.run_scoped(body,
                  xs=pltpu.VMEM((2, tb, ba * q, ell), jnp.float32),
                  cs=pltpu.VMEM((2, ba, n_lead, tk), jnp.float32),
                  sems=pltpu.SemaphoreType.DMA((2, 2)))


@functools.partial(jax.jit, static_argnames=("steps", "tk", "tb", "ba",
                                             "scale", "interpret"))
def sweep_project_pipelined(x: jnp.ndarray, *weights: jnp.ndarray, steps,
                            tk: int, tb: int, ba: int, scale: float,
                            interpret: bool) -> jnp.ndarray:
    """Double-buffered project sweep: same contract and program as
    `sweep_project`, laid out as grid = (k/TK, B/TB) with the d1 axis swept
    by an in-kernel fori_loop: the input and the leading core live in
    `memory_space=ANY` and are double-buffered into VMEM scratch by
    explicit DMAs, prefetching tile i+1 while tile i contracts."""
    b, rows, _ = x.shape
    k = weights[0].shape[-1]
    d1 = weights[-1].shape[0]
    q = rows // d1
    assert k % tk == 0 and b % tb == 0 and d1 % ba == 0, (k, tk, b, tb, d1, ba)
    in_specs = [pl.BlockSpec(memory_space=pltpu.ANY)]          # x: manual DMA
    in_specs += core_specs(weights[:-1], "project", tk=tk, ba=ba, k_pos=0,
                           lead_pos=None)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))     # leading core
    return pl.pallas_call(
        functools.partial(_project_pipelined_kernel, steps=steps,
                          scale=scale, na=d1 // ba, tk=tk, tb=tb, ba=ba,
                          q=q),
        name="sweep_project_pipelined",
        grid=(k // tk, b // tb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, tk), _imap(1, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(x, *weights)


@functools.partial(jax.jit, static_argnames=("steps", "tk", "tb", "ba",
                                             "scale", "interpret"))
def sweep_reconstruct(y: jnp.ndarray, *weights: jnp.ndarray, steps,
                      tk: int, tb: int, ba: int, scale: float,
                      interpret: bool) -> jnp.ndarray:
    """y (B, K) float32, weights per `ops.sweep_operands`. Returns the
    (B, d1*Q, L) float32 reconstruction."""
    b, k = y.shape
    d1 = weights[0].shape[0]
    ell = weights[-1].shape[-1]
    q = 1
    for step in steps[1:-1]:
        q *= step[4]
    assert k % tk == 0 and b % tb == 0 and d1 % ba == 0, (k, tk, b, tb, d1, ba)
    in_specs = [pl.BlockSpec((tb, tk), _imap(0, 2))]
    in_specs += core_specs(weights, "reconstruct", tk=tk, ba=ba, k_pos=2,
                           lead_pos=1)
    return pl.pallas_call(
        functools.partial(_reconstruct_kernel, steps=steps, scale=scale),
        name="sweep_reconstruct",
        grid=(b // tb, d1 // ba, k // tk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, ba * q, ell), _imap(0, 1, None)),
        out_shape=jax.ShapeDtypeStruct((b, d1 * q, ell), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(y, *weights)
