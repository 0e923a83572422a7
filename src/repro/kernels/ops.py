"""Public jit'd wrappers around the Pallas kernels.

``interpret=None`` (the default) lets :mod:`repro.kernels.interpret`
decide: compiled Mosaic kernels on a TPU backend, the Pallas interpreter
elsewhere (it executes the kernel body exactly, which is what the
allclose tests validate).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.icws import _token_params
from .decode_attention import decode_attention_pallas
from .icws_hash import icws_hash_grid, icws_sketch, icws_sketch_batch
from .minhash_sketch import minhash_sketch
from .ref import (decode_attention_ref, icws_sketch_ref,
                  minhash_sketch_ref, selective_scan_ref)
from .selective_scan import selective_scan_pallas


def icws_token_params(seed: int, k: int, tokens) -> tuple:
    """Host-side stateless (r, c, beta) grids (K, T) f32 for the kernels --
    identical to the ICWS family used by the index (core/icws.py)."""
    from ..core.hashing import mix2
    seeds = mix2(np.uint64(seed), np.arange(k, dtype=np.uint64))
    r = np.empty((k, len(tokens)), np.float32)
    c = np.empty_like(r)
    b = np.empty_like(r)
    for i, s in enumerate(seeds):
        ri, ci, bi = _token_params(int(s), np.asarray(tokens))
        r[i], c[i], b[i] = ri, ci, bi
    return jnp.asarray(r), jnp.asarray(c), jnp.asarray(b)


def cws_sketch(seed: int, k: int, tokens, weights, *,
               use_pallas: bool = True, interpret: bool | None = None):
    """k-coordinate CWS sketch of one text: (argmin token id, k_int) pairs.

    tokens: distinct token ids; weights: their w(t, f) > 0.
    """
    r, c, b = icws_token_params(seed, k, tokens)
    w = jnp.asarray(weights, jnp.float32)
    if use_pallas:
        mina, argt, kint = icws_sketch(r, c, b, w, interpret=interpret)
    else:
        mina, argt, kint = icws_sketch_ref(r, c, b, w)
    toks = jnp.asarray(np.asarray(tokens), jnp.int32)
    return toks[argt], kint, mina


def cws_sketch_batch(seed: int, k: int, token_lists, weight_lists, *,
                     interpret: bool | None = None):
    """CWS sketch identities for a batch of texts in ONE pallas launch.

    token_lists[b]: distinct token ids of text b; weight_lists[b]: their
    w(t, f) > 0.  Returns per-text identity lists [(token, k_int), ...] of
    length k — the sketch-coordinate format `batch_query` probes with.
    """
    B = len(token_lists)
    if B == 0:
        return []
    Tmax = max(1, max(len(t) for t in token_lists))
    r = np.empty((B, k, Tmax), np.float32)
    c = np.empty_like(r)
    be = np.empty_like(r)
    w = np.zeros((B, Tmax), np.float32)          # w<=0 masks the padding
    toks = np.zeros((B, Tmax), np.int64)
    for b, (tl, wl) in enumerate(zip(token_lists, weight_lists)):
        t = len(tl)
        rb, cb, bb = icws_token_params(seed, k, tl)
        r[b, :, :t], c[b, :, :t], be[b, :, :t] = rb, cb, bb
        r[b, :, t:] = c[b, :, t:] = be[b, :, t:] = 1.0
        w[b, :t] = np.asarray(wl, np.float32)
        toks[b, :t] = np.asarray(tl, np.int64)
    _mina, argt, kint = icws_sketch_batch(jnp.asarray(r), jnp.asarray(c),
                                          jnp.asarray(be), jnp.asarray(w),
                                          interpret=interpret)
    argt = np.asarray(argt)
    kint = np.asarray(kint)
    return [[(int(toks[b, argt[b, i]]), int(kint[b, i])) for i in range(k)]
            for b in range(B)]


def multiset_sketch(tokens, occ, seeds, *, use_pallas: bool = True,
                    interpret: bool | None = None):
    """Batched multiset min-hash sketches (B, K) u32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    occ = jnp.asarray(occ, jnp.int32)
    seeds = jnp.asarray(seeds, jnp.uint32)
    if use_pallas:
        return minhash_sketch(tokens, occ, seeds, interpret=interpret)
    return minhash_sketch_ref(tokens, occ, seeds)


def flash_decode_attention(q, k_cache, v_cache, pos, *,
                           use_pallas: bool = True,
                           interpret: bool | None = None):
    if use_pallas:
        return decode_attention_pallas(q, k_cache, v_cache, pos,
                                       interpret=interpret)
    return decode_attention_ref(q, k_cache, v_cache, pos)


def fused_selective_scan(dt, Bc, Cc, x, A, D, *, use_pallas: bool = True,
                         interpret: bool | None = None):
    if use_pallas:
        return selective_scan_pallas(dt, Bc, Cc, x, A, D,
                                     interpret=interpret)
    return selective_scan_ref(dt, Bc, Cc, x, A, D)


__all__ = ["cws_sketch", "cws_sketch_batch", "multiset_sketch",
           "flash_decode_attention", "fused_selective_scan",
           "icws_token_params", "icws_hash_grid", "icws_sketch",
           "icws_sketch_batch", "minhash_sketch", "decode_attention_pallas",
           "selective_scan_pallas"]
