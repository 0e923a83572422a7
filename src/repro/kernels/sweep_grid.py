"""Pallas TPU kernel: the grouped small-group plane sweep's coverage grid.

``repro.core.query._sweep_small_batch`` counts, for G small (query, text)
window groups at once, how many collided rectangles cover each cell of the
group's compressed boundary grid: a bincount scatter of ±1 corner pulses
followed by a double cumulative sum.  Scatter is the one primitive TPUs do
not do well, so the kernel computes the *same integer grid* through an
MXU-shaped identity: with ``xi_a/xi_b`` (``yi_c/yi_d``) the searchsorted-
left ranks of each rectangle's boundaries,

    count[i, j] = Σ_s w_s · [xi_a(s) ≤ i < xi_b(s)] · [yi_c(s) ≤ j < yi_d(s)]

— exactly the double-cumsummed pulse grid, but expressed as one batched
``dot_general`` of 0/1 stripe indicators (counts ≤ S ≤ 32, exact in f32).
Ranks need no sort (a rank is a count of strictly-smaller boundaries) and
the sorted boundary vectors ``xs``/``ys`` are reconstructed with a stable
position + one-hot gather, so every intermediate is integer-exact and the
kernel is bit-identical to the NumPy dispatcher by construction.

Padding follows the host normalization exactly: slots past ``sizes[g]``
become zero-width rectangles at the group's max boundary, contributing no
coverage and only duplicating existing compressed coordinates.

The kernel returns the hot mask (coverage ≥ m, zero-width x-stripes masked
cold) plus ``xs``/``ys`` — a few KB per batch — and the host extracts
maximal runs with the same code the NumPy path uses
(``repro.core.query._extract_runs``), which is what makes the device
plan's small-group sweep block-for-block identical to the cpu plan's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

BG = 8                         # groups per grid step (f32 sublane tile)

_NEG32 = -(1 << 30)            # int32 min // 2 (the host pad sentinel)


def _ranks_kernel_helper(bounds, vals):
    """searchsorted-left of each ``vals`` entry in its row's boundary
    multiset: rank = #{boundary < value}.  bounds (BG, NX), vals (BG, P)
    -> int32 (BG, P)."""
    lt = bounds[:, :, None] < vals[:, None, :]           # (BG, NX, P)
    return jnp.sum(lt.astype(jnp.int32), axis=1)


def _sort_rows(vals):
    """Stable ascending sort of each row without lax.sort: an element's
    sorted position is (#strictly-smaller) + (#equal at earlier index);
    the position vector is a permutation, so a one-hot masked sum places
    every element exactly once.  vals int32 (BG, NX) -> (BG, NX)."""
    n = vals.shape[1]
    lt = vals[:, :, None] < vals[:, None, :]             # vals[j] < vals[i]
    eq = vals[:, :, None] == vals[:, None, :]
    j_idx = jax.lax.broadcasted_iota(jnp.int32, lt.shape, 1)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, lt.shape, 2)
    pos = jnp.sum((lt | (eq & (j_idx < i_idx))).astype(jnp.int32), axis=1)
    p_idx = jax.lax.broadcasted_iota(jnp.int32, (vals.shape[0], n, n), 2)
    onehot = pos[:, :, None] == p_idx                    # (BG, NX, NX)
    return jnp.sum(jnp.where(onehot, vals[:, :, None], 0), axis=1)


def _sweep_kernel(a_ref, b_ref, c_ref, d_ref, size_ref,
                  hot_ref, xs_ref, ys_ref, *, m: int):
    a = a_ref[...]                                       # (BG, S) int32
    b1 = b_ref[...] + 1
    c = c_ref[...]
    d1 = d_ref[...] + 1
    sizes = size_ref[...]                                # (BG, 1) int32
    slot = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    pad = slot >= sizes                                  # (BG, S)

    # host-identical padding normalization: zero-width rects at the
    # group's max exclusive boundary (duplicates an existing coordinate)
    neg = jnp.int32(_NEG32)
    bmax = jnp.max(jnp.where(pad, neg, b1), axis=1, keepdims=True)
    dmax = jnp.max(jnp.where(pad, neg, d1), axis=1, keepdims=True)
    a = jnp.where(pad, bmax, a)
    b1 = jnp.where(pad, bmax, b1)
    c = jnp.where(pad, dmax, c)
    d1 = jnp.where(pad, dmax, d1)

    bx = jnp.concatenate([a, b1], axis=1)                # (BG, NX)
    by = jnp.concatenate([c, d1], axis=1)
    xi_a = _ranks_kernel_helper(bx, a)
    xi_b = _ranks_kernel_helper(bx, b1)
    yi_c = _ranks_kernel_helper(by, c)
    yi_d = _ranks_kernel_helper(by, d1)

    # coverage as an indicator matmul (the cumsummed pulse grid, exactly)
    nx = bx.shape[1]
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], a.shape[1], nx),
                                     2)
    w = jnp.where(pad, 0.0, 1.0).astype(jnp.float32)
    xind = ((xi_a[:, :, None] <= i_idx) & (i_idx < xi_b[:, :, None]))
    xind = xind.astype(jnp.float32) * w[:, :, None]
    yind = ((yi_c[:, :, None] <= i_idx) & (i_idx < yi_d[:, :, None]))
    count = jax.lax.dot_general(
        xind, yind.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (BG, NX, NX)
    count = count.astype(jnp.int32)                      # counts <= S: exact

    xs = _sort_rows(bx)
    ys = _sort_rows(by)
    # zero-width x stripes are cold (each x stripe emits its own block);
    # zero-width y stripes pass through run extraction unchanged, as on
    # the host
    nz = jnp.concatenate(
        [(xs[:, 1:] > xs[:, :-1]).astype(jnp.int32),
         jnp.zeros((xs.shape[0], 1), jnp.int32)], axis=1)
    hot_ref[...] = jnp.where(count >= m, nz[:, :, None], 0)
    xs_ref[...] = xs
    ys_ref[...] = ys


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def sweep_grid(rects, sizes, *, m: int, interpret: bool | None = None):
    """Coverage grids for G padded rectangle groups, one Pallas launch.

    rects int32 (G, S, 4) — (a, b, c, d) rows, slots past ``sizes[g]``
    ignored; sizes int32 (G,).  Returns (hot int32 (G, NX, NX), xs int32
    (G, NX), ys int32 (G, NX)) with NX = 2S; the host consumes
    ``hot[:, :NX-1, :NX-1]`` (stripe i spans ``xs[i]..xs[i+1]-1``).
    """
    G, S, _ = rects.shape
    NX = 2 * S
    Gp = max(BG, -(-G // BG) * BG)
    rects = jnp.pad(jnp.asarray(rects, jnp.int32),
                    ((0, Gp - G), (0, 0), (0, 0)))
    sizes = jnp.pad(jnp.asarray(sizes, jnp.int32), (0, Gp - G))[:, None]
    kern = functools.partial(_sweep_kernel, m=m)
    hot, xs, ys = pl.pallas_call(
        kern,
        grid=(Gp // BG,),
        in_specs=[
            pl.BlockSpec((BG, S), lambda g: (g, 0)),
            pl.BlockSpec((BG, S), lambda g: (g, 0)),
            pl.BlockSpec((BG, S), lambda g: (g, 0)),
            pl.BlockSpec((BG, S), lambda g: (g, 0)),
            pl.BlockSpec((BG, 1), lambda g: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BG, NX, NX), lambda g: (g, 0, 0)),
            pl.BlockSpec((BG, NX), lambda g: (g, 0)),
            pl.BlockSpec((BG, NX), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Gp, NX, NX), jnp.int32),
            jax.ShapeDtypeStruct((Gp, NX), jnp.int32),
            jax.ShapeDtypeStruct((Gp, NX), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(rects[..., 0], rects[..., 1], rects[..., 2], rects[..., 3], sizes)
    return hot[:G], xs[:G], ys[:G]


def sweep_small_batch_device(arr: np.ndarray, sizes: np.ndarray, m: int
                             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(G, S, 4) rect rows — host rows, uploaded here, or rows already
    on the device — -> (hot bool (G, NX-1, NX-1), xs (G, NX), ys (G, NX))
    as NumPy, ready for ``_extract_runs``.  The device small-group sweep
    of ``repro.core.query.sweep_groups``."""
    hot, xs, ys = sweep_grid(jnp.asarray(arr, jnp.int32),
                             jnp.asarray(sizes, jnp.int32), m=int(m))
    NX = xs.shape[1]
    # cast to bool on-device: the coverage grid crosses the bus at one
    # byte per cell instead of four
    return (np.asarray(hot[:, :NX - 1, :NX - 1].astype(jnp.bool_)),
            np.asarray(xs, np.int64), np.asarray(ys, np.int64))
