"""Pallas TPU kernels for ICWS (improved consistent weighted sampling).

Three kernels over the (K hash functions x T distinct tokens) grid:

* `icws_hash_grid`    -- materializes (k_int, a) for every (k, t): feeds the
  MonoActive partitioner's active-hash generation (the paper's indexing
  hot loop).
* `icws_sketch`       -- fused hash + running arg-min reduction: produces
  the k-coordinate CWS sketch of a text without materializing the grid (one
  HBM pass; this is the query/sketching fast path).
* `icws_sketch_batch` -- the same fused reduction with a leading batch grid
  axis: the sketches of a whole query batch in ONE pallas launch (the
  `batch_query` serving path).

Tiling: (BK, BT) = (8, 128) f32 blocks in VMEM -- one (sublane x lane)
register tile per step; the grid's T axis is innermost so the arg-min
accumulates sequentially into the (BK,) output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

BK, BT = 8, 128
_BIG = 3.0e38  # python literal: pallas kernels cannot capture array constants


def _hash_grid_kernel(r_ref, c_ref, b_ref, w_ref, kint_ref, a_ref):
    r = r_ref[...]
    c = c_ref[...]
    beta = b_ref[...]
    w = w_ref[...]                      # (1, BT) -- broadcast over K rows
    valid = w > 0.0
    lw = jnp.log(jnp.where(valid, w, 1.0))
    kint = jnp.floor(lw / r + beta)
    a = c * jnp.exp(-r * (kint - beta) - r)
    kint_ref[...] = jnp.where(valid, kint, 0.0).astype(jnp.int32)
    a_ref[...] = jnp.where(valid, a, _BIG)


@functools.partial(jax.jit, static_argnames=("interpret",))
def icws_hash_grid(r, c, beta, w, *, interpret: bool | None = None):
    """r,c,beta (K,T) f32; w (T,) f32 (w<=0 = masked) -> (kint i32, a f32)."""
    K, T = r.shape
    Kp, Tp = -(-K // BK) * BK, -(-T // BT) * BT
    pad2 = lambda x: jnp.pad(x, ((0, Kp - K), (0, Tp - T)), constant_values=1.0)
    wp = jnp.pad(w, (0, Tp - T))[None, :]
    grid = (Kp // BK, Tp // BT)
    kint, a = pl.pallas_call(
        _hash_grid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((1, BT), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, Tp), jnp.int32),
            jax.ShapeDtypeStruct((Kp, Tp), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(pad2(r), pad2(c), pad2(beta), wp)
    return kint[:K, :T], a[:K, :T]


def _block_argmin(a, kint):
    """Row-wise (min, first argmin, kint at the argmin) of a (BK, BT)
    block, each (BK, 1): a min-reduce, then the smallest lane index that
    attains it (``jnp.argmin``'s tie rule), then ``kint`` picked by an
    iota mask — Mosaic lowers no gather or variadic argmin reduce."""
    amin = jnp.min(a, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    loc = jnp.min(jnp.where(a == amin, lane, a.shape[1]), axis=1,
                  keepdims=True)
    kmin = jnp.sum(jnp.where(lane == loc, kint.astype(jnp.int32), 0),
                   axis=1, keepdims=True)
    return amin, loc, kmin


def _sketch_kernel(r_ref, c_ref, b_ref, w_ref,
                   mina_ref, argt_ref, kint_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mina_ref[...] = jnp.full(mina_ref.shape, _BIG, mina_ref.dtype)
        argt_ref[...] = jnp.full(argt_ref.shape, -1, argt_ref.dtype)
        kint_ref[...] = jnp.zeros(kint_ref.shape, kint_ref.dtype)

    r = r_ref[...]
    c = c_ref[...]
    beta = b_ref[...]
    w = w_ref[...]
    valid = w > 0.0
    lw = jnp.log(jnp.where(valid, w, 1.0))
    kint = jnp.floor(lw / r + beta)
    a = jnp.where(valid, c * jnp.exp(-r * (kint - beta) - r), _BIG)

    amin, loc, kmin = _block_argmin(a, kint)          # (BK, 1) each
    upd = amin < mina_ref[...]
    mina_ref[...] = jnp.where(upd, amin, mina_ref[...])
    argt_ref[...] = jnp.where(upd, j * BT + loc, argt_ref[...])
    kint_ref[...] = jnp.where(upd, kmin, kint_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def icws_sketch(r, c, beta, w, *, interpret: bool | None = None):
    """Fused CWS sketch: -> (min_a (K,), argmin_token (K,), k_int (K,))."""
    K, T = r.shape
    Kp, Tp = -(-K // BK) * BK, -(-T // BT) * BT
    pad2 = lambda x: jnp.pad(x, ((0, Kp - K), (0, Tp - T)), constant_values=1.0)
    wp = jnp.pad(w, (0, Tp - T))[None, :]
    grid = (Kp // BK, Tp // BT)
    mina, argt, kint = pl.pallas_call(
        _sketch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((BK, BT), lambda i, j: (i, j)),
            pl.BlockSpec((1, BT), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((BK, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BK, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BK, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Kp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Kp, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(pad2(r), pad2(c), pad2(beta), wp)
    return mina[:K, 0], argt[:K, 0], kint[:K, 0]


def _sketch_batch_kernel(r_ref, c_ref, b_ref, w_ref,
                         mina_ref, argt_ref, kint_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        mina_ref[...] = jnp.full(mina_ref.shape, _BIG, mina_ref.dtype)
        argt_ref[...] = jnp.full(argt_ref.shape, -1, argt_ref.dtype)
        kint_ref[...] = jnp.zeros(kint_ref.shape, kint_ref.dtype)

    r = r_ref[0]                        # (BK, BT)
    c = c_ref[0]
    beta = b_ref[0]
    w = w_ref[0]                        # (1, BT) -- broadcast over K rows
    valid = w > 0.0
    lw = jnp.log(jnp.where(valid, w, 1.0))
    kint = jnp.floor(lw / r + beta)
    a = jnp.where(valid, c * jnp.exp(-r * (kint - beta) - r), _BIG)

    amin, loc, kmin = _block_argmin(a, kint)          # (BK, 1) each
    upd = amin < mina_ref[0]
    mina_ref[0] = jnp.where(upd, amin, mina_ref[0])
    argt_ref[0] = jnp.where(upd, j * BT + loc, argt_ref[0])
    kint_ref[0] = jnp.where(upd, kmin, kint_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def icws_sketch_batch(r, c, beta, w, *, interpret: bool | None = None):
    """Batched fused CWS sketch, one launch for the whole query batch.

    r,c,beta (B,K,T) f32; w (B,T) f32 (w<=0 = padding mask) ->
    (min_a (B,K) f32, argmin_token (B,K) i32, k_int (B,K) i32).
    """
    B, K, T = r.shape
    Kp, Tp = -(-K // BK) * BK, -(-T // BT) * BT
    pad3 = lambda x: jnp.pad(x, ((0, 0), (0, Kp - K), (0, Tp - T)),
                             constant_values=1.0)
    wp = jnp.pad(w, ((0, 0), (0, Tp - T)))[:, None, :]
    grid = (B, Kp // BK, Tp // BT)
    mina, argt, kint = pl.pallas_call(
        _sketch_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BK, BT), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, BK, BT), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, BK, BT), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, 1, BT), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, BK, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Kp, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, Kp, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(pad3(r), pad3(c), pad3(beta), wp)
    return mina[:, :K, 0], argt[:, :K, 0], kint[:, :K, 0]
