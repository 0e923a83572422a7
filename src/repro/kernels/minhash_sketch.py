"""Pallas TPU kernel: batched multi-set min-hash sketches.

Computes, for a batch of (padded) token streams, the k-coordinate multi-set
min-hash sketch min over positions of h_k(token, occurrence-index) -- the
device-side half of the paper's pipeline (the host partitioner consumes
per-text sketches; the data-pipeline dedup filter consumes per-document
sketches at corpus scale).

Grid: (B, K/BK, N/BN); the N axis is innermost and accumulates a running
min into the (1, BK) output block.  Hashing is the 32-bit counter family
(common.py) -- TPU has no 64-bit integer VPU lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .common import hash32
from .interpret import resolve_interpret

BK, BN = 8, 128
_U32MAX = np.uint32(0xFFFFFFFF)


def _minhash_kernel(tok_ref, occ_ref, seed_ref, out_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, _U32MAX, out_ref.dtype)

    toks = tok_ref[...]                     # (1, BN) i32
    occ = occ_ref[...]                      # (1, BN) i32
    seeds = seed_ref[...]                   # (1, BK) u32
    valid = toks >= 0
    h = hash32(seeds[0][:, None], toks[0][None, :].astype(jnp.uint32),
               occ[0][None, :].astype(jnp.uint32))          # (BK, BN)
    h = jnp.where(valid[0][None, :], h, _U32MAX)
    out_ref[0, :] = jnp.minimum(out_ref[0, :], jnp.min(h, axis=1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def minhash_sketch(tokens, occ, seeds, *, interpret: bool | None = None):
    """tokens (B,N) i32 (pad=-1), occ (B,N) i32 (1-based occurrence index),
    seeds (K,) u32 -> sketches (B,K) u32."""
    B, N = tokens.shape
    K = seeds.shape[0]
    Kp, Np = -(-K // BK) * BK, -(-N // BN) * BN
    tok = jnp.pad(tokens, ((0, 0), (0, Np - N)), constant_values=-1)
    occ = jnp.pad(occ, ((0, 0), (0, Np - N)))
    sd = jnp.pad(seeds, (0, Kp - K))[None, :]
    out = pl.pallas_call(
        _minhash_kernel,
        grid=(B, Kp // BK, Np // BN),
        in_specs=[
            pl.BlockSpec((1, BN), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, BN), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, BK), lambda b, i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, BK), lambda b, i, j: (b, i)),
        out_shape=jax.ShapeDtypeStruct((B, Kp), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(tok, occ, sd)
    return out[:, :K]
