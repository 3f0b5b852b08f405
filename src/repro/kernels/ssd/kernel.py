"""Mamba2 SSD (state-space duality) chunked Pallas kernel.

Grid (b, H, S/chunk), chunk axis sequential with the (P, N) state in VMEM
scratch. Per chunk the decay matrix M[t,s] = (C_t.B_s) exp(Li[t]-Li[s]) dt_s
(s<=t) is a plain (chunk x chunk) MXU operand per head — the SSD insight
that the scan can be expressed as matmuls maps directly onto the MXU, with
the cross-chunk recurrence carried in registers/VMEM rather than CUDA's
shared-memory warp accumulators (HW adaptation noted in DESIGN.md).

VMEM per step @ chunk=128, P=64, N=64: x/B/C tiles + M (128x128 f32) +
state (64x64 f32) ~= 0.4 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, s0_ref,
            y_ref, sout_ref, s_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    f32 = jnp.float32
    xx = x_ref[0, :, 0, :].astype(f32)  # (C,P)
    dd = dt_ref[0, :, 0].astype(f32)  # (C,)
    BB = b_ref[0].astype(f32)  # (C,N)
    CC = c_ref[0].astype(f32)  # (C,N)
    A = -jnp.exp(alog_ref[0].astype(f32))  # scalar
    Dv = d_ref[0].astype(f32)

    la = dd * A  # (C,)
    Li = jnp.cumsum(la)
    cb = jax.lax.dot_general(CC, BB, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)  # (C,C)
    G = jnp.exp(jnp.clip(Li[:, None] - Li[None, :], -60.0, 0.0))
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    M = jnp.where(mask, cb * G * dd[None, :], 0.0)
    y = jax.lax.dot_general(M, xx, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)  # (C,P)
    # incoming state: y += exp(Li)[:,None] * (CC @ state^T)
    h_in = s_scr[...]  # (P,N)
    y += jnp.exp(Li)[:, None] * jax.lax.dot_general(
        CC, h_in, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    y += xx * Dv
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    # state update: h_out = exp(Li[-1]) h_in + (w*x)^T @ B
    wgt = jnp.exp(Li[-1] - Li) * dd  # (C,)
    upd = jax.lax.dot_general(wgt[:, None] * xx, BB, (((0,), (0,)), ((), ())),
                              preferred_element_type=f32)  # (P,N)
    s_scr[...] = jnp.exp(Li[-1]) * h_in + upd

    @pl.when(ic == n_chunks - 1)
    def _fin():
        sout_ref[0, 0] = s_scr[...]


def ssd_chunked(x, dt, B, C, A_log, D, state, *, chunk=128, interpret=False):
    """Shapes as in ref.ssd. Returns (y f32, state_out f32)."""
    b, S, H, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0
    n = S // chunk
    grid = (b, H, n)
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=n)
    y, sout = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bb, h, ic: (bb, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bb, h, ic: (bb, ic, h)),
            pl.BlockSpec((1, chunk, N), lambda bb, h, ic: (bb, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bb, h, ic: (bb, ic, 0)),
            pl.BlockSpec((1,), lambda bb, h, ic: (h,)),
            pl.BlockSpec((1,), lambda bb, h, ic: (h,)),
            pl.BlockSpec((1, 1, P, N), lambda bb, h, ic: (bb, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bb, h, ic: (bb, ic, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bb, h, ic: (bb, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, A_log, D, state)
    return y, sout
