"""Pallas TPU kernel: fused Mamba-1 selective scan.

The CUDA selective-scan keeps per-channel states resident in SRAM while
streaming the sequence; the TPU adaptation tiles channels into VMEM blocks
(BD x d_state f32 state scratch) and streams sequence chunks HBM->VMEM.
Grid (B, di/BD, S/BS): the S axis is innermost/sequential, so the state
scratch carries across chunks -- per-step states never round-trip to HBM
(vs. the XLA associative-scan path, which materializes log-depth
(B, chunk, di, ds) tensors; see DESIGN.md §2.1 and EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

BD, BS = 128, 64


def _sel_scan_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, d_ref,
                     y_ref, hout_ref, h_scr):
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, h_scr.dtype)

    A = a_ref[...]                           # (BD, ds)
    D = d_ref[...]                           # (1, BD)

    def step(t, h):
        row = lambda ref: ref[0, pl.ds(t, 1), :][0]
        dt_t = row(dt_ref)                   # (BD,)
        x_t = row(x_ref)
        B_t = row(b_ref)                     # (ds,)
        C_t = row(c_ref)
        a = jnp.exp(dt_t[:, None] * A)       # (BD, ds)
        h = a * h + (dt_t * x_t)[:, None] * B_t[None, :]
        y_t = jnp.sum(h * C_t[None, :], axis=1) + D[0] * x_t
        y_ref[0, pl.ds(t, 1), :] = y_t[None, :].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, dt_ref.shape[1], step, h_scr[...])
    h_scr[...] = h

    @pl.when(s == ns - 1)
    def _fin():
        hout_ref[0, :, :] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_pallas(dt, Bc, Cc, x, A, D, *,
                          interpret: bool | None = None):
    """dt,x (B,S,di) f32; Bc,Cc (B,S,ds) f32; A (di,ds) f32 (negative);
    D (di,) -> (y (B,S,di) f32, h_final (B,di,ds) f32).

    Computes h_t = exp(dt_t*A) h_{t-1} + dt_t*B_t*x_t; y_t = h_t.C_t + D*x_t.
    """
    B, S, di = x.shape
    ds = A.shape[1]
    Dp = -(-di // BD) * BD
    Sp = -(-S // BS) * BS
    pad3 = lambda t: jnp.pad(t, ((0, 0), (0, Sp - S), (0, Dp - di)))
    pads = lambda t: jnp.pad(t, ((0, 0), (0, Sp - S), (0, 0)))
    A_p = jnp.pad(A, ((0, Dp - di), (0, 0)), constant_values=-1.0)
    D_p = jnp.pad(D, (0, Dp - di))[None, :]
    grid = (B, Dp // BD, Sp // BS)
    y, hf = pl.pallas_call(
        _sel_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BS, BD), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((1, BS, ds), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((1, BS, ds), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((1, BS, BD), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((BD, ds), lambda b, d, s: (d, 0)),
            pl.BlockSpec((1, BD), lambda b, d, s: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, BS, BD), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((1, BD, ds), lambda b, d, s: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, Dp, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((BD, ds), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(pad3(dt), pads(Bc), pads(Cc), pad3(x), A_p, D_p)
    return y[:, :S, :di], hf[:, :di, :]
