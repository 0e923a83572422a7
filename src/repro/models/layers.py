"""Shared neural building blocks: RMSNorm, RoPE, GQA attention, SwiGLU, MoE.

All functions are pure; dtypes follow cfg.compute_dtype with f32 softmax /
norm statistics.  Attention supports causal masking, sliding windows
(mixtral), query chunking (memory-bounded 32k prefill), and single-token
decode against a KV cache.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def rms_norm(x, gamma, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


def rope_angles(positions, head_dim: int, theta: float):
    """positions (...,) int32 -> cos/sin (..., head_dim//2) f32."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B,S,H,D); cos/sin (B,S,D/2) or (S,D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _scores_mask(q_pos, k_pos, window: int):
    """(Sq, Sk) additive mask: causal + optional sliding window."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _constrain(x, mesh, *logical):
    if mesh is None:
        return x
    from ..sharding import constrain
    return constrain(x, mesh, *logical)


def _attend(q, k, v, mask, mesh=None, cp=False):
    """q (B,Sq,H,D), k/v (B,Sk,H,D) (kv already repeated to H heads).

    cp=True: context parallelism -- shard the query rows over `model`
    (used when n_heads does not divide the TP width; kv stays replicated).
    """
    B, Sq, H, D = q.shape
    if cp:
        q = _constrain(q, mesh, "batch", "seq_sp", None, None)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / math.sqrt(D)
    s = s.astype(jnp.float32)
    if mask is not None:
        s = s + mask[None, None]
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqs,bshd->bqhd", w, v)
    if cp:
        o = _constrain(o, mesh, "batch", "seq_sp", None, None)
    return o


def repeat_kv(k, n_heads: int):
    """(B,S,KV,D) -> (B,S,H,D): Megatron-style KV duplication so head
    sharding is uniform even when TP width > n_kv_heads."""
    KV = k.shape[2]
    if KV == n_heads:
        return k
    return jnp.repeat(k, n_heads // KV, axis=2)


def attention(q, k, v, *, q_offset=0, window: int = 0, q_chunk: int = 0,
              mesh=None, cp=False):
    """Causal GQA attention.  q (B,Sq,H,D); k,v (B,Sk,KV,D).

    q_offset: absolute position of q[0] relative to k[0] (prefill: 0).
    q_chunk:  if >0 and Sq > q_chunk, scan over query chunks (memory).
    cp:       context-parallel fallback (when heads % TP width != 0).
    """
    H = q.shape[2]
    if cp:
        k = _constrain(k, mesh, "batch", None, None, None)
        v = _constrain(v, mesh, "batch", None, None, None)
    k, v = repeat_kv(k, H), repeat_kv(v, H)
    if not cp and mesh is not None:
        q = _constrain(q, mesh, "batch", None, "heads", "head_dim")
        k = _constrain(k, mesh, "batch", None, "heads", "head_dim")
        v = _constrain(v, mesh, "batch", None, "heads", "head_dim")
    Sq, Sk = q.shape[1], k.shape[1]
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    if not q_chunk or Sq <= q_chunk or Sq % q_chunk:
        return _attend(q, k, v, _scores_mask(q_pos, k_pos, window), mesh, cp)

    n = Sq // q_chunk

    def body(_, qc_i):
        qc, i = qc_i
        qp = q_offset + i * q_chunk + jnp.arange(q_chunk)
        mask = jnp.where(
            (k_pos[None, :] <= qp[:, None])
            & ((k_pos[None, :] > qp[:, None] - window) if window else True),
            0.0, NEG_INF).astype(jnp.float32)
        return None, _attend(qc, k, v, mask, mesh, cp)

    qs = q.reshape(q.shape[0], n, q_chunk, *q.shape[2:]).swapaxes(0, 1)
    _, outs = lax.scan(body, None, (qs, jnp.arange(n)))
    outs = outs.swapaxes(0, 1)
    return outs.reshape(q.shape)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     mesh=None):
    """q (B,1,H,D) vs cache (B,Smax,KV,D); positions > pos are masked.

    Split-KV (flash-decode) sharding: the cache stays sharded on seq
    (`seq_kv` -> model); scores/softmax-stats are computed per KV shard with
    explicit constraints so GSPMD never gathers the cache (the unconstrained
    einsum replicated it -- 9.8 TB/device on llama3-405b decode_32k,
    EXPERIMENTS.md §Perf cell B).  The Pallas `decode_attention` kernel is
    the fused single-chip version of the same schedule.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    Smax = k_cache.shape[1]
    k_pos = jnp.arange(Smax)
    ok = k_pos <= pos
    if window:
        ok &= k_pos > pos - window
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    qh = q.reshape(B, KV, G, D)
    if mesh is not None:
        qh = _constrain(qh, mesh, "batch", None, None, None)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache) / math.sqrt(D)
    s = s.astype(jnp.float32) + mask[None, None, None]
    if mesh is not None:
        s = _constrain(s, mesh, "batch", None, None, "seq_kv")
    # softmax over the sharded axis: XLA partitions max/sum with small psums
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", w, v_cache)
    if mesh is not None:
        o = _constrain(o, mesh, "batch", None, None, None)
    return o.reshape(B, 1, H, D)


def swiglu(x, w1, w3, w2):
    h = jnp.einsum("bsd,df->bsf", x, w1)
    g = jnp.einsum("bsd,df->bsf", x, w3)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(h) * g, w2)


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------

def moe_router(xf, router_w, top_k: int):
    """xf (N,d) -> gates (N,k) f32 (softmax over selected), idx (N,k) i32."""
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    gate_logits, idx = lax.top_k(logits, top_k)
    gates = jax.nn.softmax(gate_logits, axis=-1)
    return gates, idx


def moe_dense(x, router_w, w1, w3, w2, top_k: int):
    """Reference all-experts path (smoke tests / correctness oracle)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    gates, idx = moe_router(xf, router_w, top_k)
    h = jnp.einsum("nd,edf->nef", xf, w1)
    g = jnp.einsum("nd,edf->nef", xf, w3)
    y = jnp.einsum("nef,efd->ned", jax.nn.silu(h) * g, w2)   # (N,E,d)
    sel = jnp.take_along_axis(y, idx[:, :, None], axis=1)     # (N,k,d)
    out = jnp.sum(sel * gates[:, :, None].astype(sel.dtype), axis=1)
    return out.reshape(B, S, d)


def moe_scatter(x, router_w, w1, w3, w2, top_k: int,
                capacity_factor: float = 1.25, mesh=None):
    """Production path: *group-local* sort-based dispatch into per-expert
    capacity buffers (grouped matmul), Switch-Transformer style.

    The batch dim is the dispatch group: every scatter/gather is local to a
    data-parallel shard (a global argsort over all tokens forces GSPMD to
    replicate the (N, d) activations -- measured 106 TB/device of collective
    traffic on qwen3-moe train_4k; see EXPERIMENTS.md §Perf cell A).  The
    (group, expert) buffer is then resharded expert-parallel -- one
    all-to-all, the EP exchange -- so expert matmuls run with E local to the
    `model` axis.  Tokens over an expert's per-group capacity are dropped
    (capacity-factor routing).
    """
    B, S, d = x.shape
    E = router_w.shape[-1]
    C = max(1, math.ceil(S * top_k * capacity_factor / E))
    gates, idx = moe_router(x.reshape(-1, d), router_w, top_k)
    gates = gates.reshape(B, S * top_k)
    flat_e = idx.reshape(B, S * top_k)

    order = jnp.argsort(flat_e, axis=-1, stable=True)         # (B, S*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    ar = jnp.arange(S * top_k, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((B, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    seg_start = lax.cummax(jnp.where(change, ar, 0), axis=1)
    slot = ar - seg_start                                     # rank in expert
    keep = slot < C
    dest = jnp.where(keep, sorted_e * C + slot, E * C)        # E*C = dropped
    tok = order // top_k                                      # (B, S*k)

    rows = jnp.arange(B)[:, None]
    xf = x  # (B, S, d)
    vals = jnp.take_along_axis(
        xf, tok[..., None].astype(jnp.int32), axis=1)         # (B, S*k, d)
    if mesh is not None:
        from ..sharding import constrain
        # GSPMD's batched-gather partitioning can fall back to replicating
        # the (B, S*k, d) routed copies at global size (measured 12 TB/dev
        # of gathers on qwen3-moe prefill_32k); pin it to the batch shards.
        vals = constrain(vals, mesh, "batch", None, "embed_act")
    buf = jnp.zeros((B, E * C + 1, d), x.dtype).at[rows, dest].set(vals)
    buf = buf[:, :-1].reshape(B, E, C, d)
    if mesh is not None:
        from ..sharding import constrain
        # two explicit stages (§Perf cell A iteration 2): (1) the scatter
        # lands batch-local / expert-UNsharded -- GSPMD partitions a scatter
        # across the expert axis as partial buffers + a full-size all-reduce
        # (103 TB/device measured); (2) the dense reshard to expert-parallel
        # is then one all-to-all of exactly the routed tokens.
        buf = constrain(buf, mesh, "batch", None, None, "embed_act")
        buf = constrain(buf, mesh, "batch", "experts", None, "embed_act")
    h = jnp.einsum("becd,edf->becf", buf, w1)
    g = jnp.einsum("becd,edf->becf", buf, w3)
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(h) * g, w2)
    if mesh is not None:
        from ..sharding import constrain
        y = constrain(y, mesh, "batch", "experts", None, "embed_act")
        # return to batch-local before the combine gather (mirror all-to-all)
        y = constrain(y, mesh, "batch", None, None, "embed_act")
    yf = jnp.concatenate([y.reshape(B, E * C, d),
                          jnp.zeros((B, 1, d), y.dtype)], axis=1)
    contrib = jnp.take_along_axis(yf, dest[..., None], axis=1) \
        * (gates_sorted := jnp.take_along_axis(gates, order, axis=-1)
           )[..., None].astype(y.dtype) * keep[..., None]
    if mesh is not None:
        from ..sharding import constrain
        contrib = constrain(contrib, mesh, "batch", None, "embed_act")
    out = jnp.zeros((B, S, d), x.dtype).at[rows, tok].add(contrib)
    return out


def _dispatch_local(x, gates, idx, E: int, C: int):
    """Row-local sort-based dispatch (no mesh interaction).
    x (B,S,d); idx (B*S, k) -> buf (B,E,C,d) + combine metadata."""
    B, S, d = x.shape
    top_k = idx.shape[-1]
    flat_e = idx.reshape(B, S * top_k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    ar = jnp.arange(S * top_k, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((B, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    seg_start = lax.cummax(jnp.where(change, ar, 0), axis=1)
    slot = ar - seg_start
    keep = slot < C
    dest = jnp.where(keep, sorted_e * C + slot, E * C)
    tok = order // top_k
    rows = jnp.arange(B)[:, None]
    vals = jnp.take_along_axis(x, tok[..., None].astype(jnp.int32), axis=1)
    buf = jnp.zeros((B, E * C + 1, d), x.dtype).at[rows, dest].set(vals)
    return buf[:, :-1].reshape(B, E, C, d), (dest, keep, tok, order, rows)


def _combine_local(y, gates, meta, B, S, d, E, C, top_k):
    """Inverse of _dispatch_local: gather expert outputs back per token."""
    dest, keep, tok, order, rows = meta
    yf = jnp.concatenate([y.reshape(B, E * C, d),
                          jnp.zeros((B, 1, d), y.dtype)], axis=1)
    g_sorted = jnp.take_along_axis(gates.reshape(B, S * top_k), order,
                                   axis=-1)
    contrib = jnp.take_along_axis(yf, dest[..., None], axis=1) \
        * g_sorted[..., None].astype(y.dtype) * keep[..., None]
    return jnp.zeros((B, S, d), y.dtype).at[rows, tok].add(contrib)


def moe_shardmap(x, router_w, w1, w3, w2, top_k: int,
                 capacity_factor: float, mesh):
    """Expert parallelism with explicit collectives (shard_map).

    GSPMD partitions data-dependent gather/scatter by replication (measured
    12 TB/device on qwen3-moe prefill); inside shard_map every dispatch op
    is shard-local by construction and the EP exchange is two explicit
    tiled all-to-alls + one sequence all-gather:

      tokens seq-split over `model` -> local top-k dispatch ->
      all_to_all (experts <-> capacity) -> local grouped matmul ->
      all_to_all back -> local combine -> all_gather seq chunks.

    Requires E % tp == 0 and S % tp == 0 (caller falls back to
    `moe_scatter` otherwise).
    """
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    E = w1.shape[0]
    tp = mesh.shape["model"]
    batch = tuple(a for a in ("pod", "data") if a in mesh.shape)
    S_loc = S // tp
    C = max(1, math.ceil(S_loc * top_k * capacity_factor / E))

    def body(xl, wr, w1l, w3l, w2l):
        r = lax.axis_index("model")
        xs = lax.dynamic_slice_in_dim(xl, r * S_loc, S_loc, axis=1)
        gates, idx = moe_router(xs.reshape(-1, d), wr, top_k)
        buf, meta = _dispatch_local(xs, gates, idx, E, C)      # (B,E,C,d)
        recv = lax.all_to_all(buf, "model", split_axis=1, concat_axis=2,
                              tiled=True)                      # (B,E/tp,C*tp,d)
        h = jnp.einsum("becd,edf->becf", recv, w1l)
        g = jnp.einsum("becd,edf->becf", recv, w3l)
        y = jnp.einsum("becf,efd->becd", jax.nn.silu(h) * g, w2l)
        back = lax.all_to_all(y, "model", split_axis=2, concat_axis=1,
                              tiled=True)                      # (B,E,C,d)
        out = _combine_local(back, gates, meta, xs.shape[0], S_loc, d, E, C,
                             top_k)
        return lax.all_gather(out, "model", axis=1, tiled=True)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch, None, None), check_vma=False)
    return fn(x, router_w, w1, w3, w2)


def causal_conv1d(x, w, b):
    """Depthwise causal conv.  x (B,S,C), w (K,C), b (C)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return out + b
