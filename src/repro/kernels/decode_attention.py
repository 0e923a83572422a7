"""Pallas TPU kernel: flash-decode (split-KV online-softmax) attention.

One new query token per sequence attends to a long KV cache.  Grid
(B, H, S/BS): the S axis is innermost; running (m, l, acc) statistics live
in VMEM scratch and accumulate across KV tiles, so the cache streams through
VMEM exactly once (the decode step is HBM-bandwidth-bound; see §Roofline).
GQA is folded into the k/v BlockSpec index map (h -> h // group) -- no
repeated KV is ever materialized.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

BS = 128
_NEG = -1.0e30


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr):
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    q = q_ref[0, 0, :]                                  # (D,)
    k = k_ref[0, :, 0, :]                               # (BS, D)
    v = v_ref[0, :, 0, :]
    pos = pos_ref[0]
    idx = s * BS + jax.lax.iota(jnp.int32, BS)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.sum(q[None, :].astype(jnp.float32)
                     * k.astype(jnp.float32), axis=-1) * scale
    scores = jnp.where(idx <= pos, scores, _NEG)

    m_prev = m_scr[0]
    m_new = jnp.maximum(m_prev, jnp.max(scores))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_scr[0] = l_scr[0] * alpha + jnp.sum(p)
    acc_scr[...] = acc_scr[...] * alpha + \
        jnp.sum(p[:, None] * v.astype(jnp.float32), axis=0)[None]
    m_scr[0] = m_new

    @pl.when(s == ns - 1)
    def _fin():
        o_ref[0, 0, :] = (acc_scr[0] / l_scr[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_pallas(q, k_cache, v_cache, pos, *,
                            interpret: bool | None = None):
    """q (B,H,D); k/v cache (B,S,KV,D); pos scalar i32 -> out (B,H,D)."""
    B, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    Sp = -(-S // BS) * BS
    if Sp != S:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    pos_arr = jnp.full((1,), pos, jnp.int32)
    grid = (B, H, Sp // BS)
    out = pl.pallas_call(
        _decode_attn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (0,)),
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, BS, 1, D), lambda b, h, s: (b, s, h // G, 0)),
            pl.BlockSpec((1, BS, 1, D), lambda b, h, s: (b, s, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(pos_arr, q, k_cache, v_cache)
    return out
