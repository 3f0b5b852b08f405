"""WKV6 chunked linear-attention Pallas kernel.

Grid (B, H, S/chunk) with the chunk axis sequential ("arbitrary") so the
per-(b,h) running state S in R^{K x V} lives in VMEM scratch across chunk
steps — the cross-chunk recurrence never touches HBM. Within a chunk the
exact per-channel decay tensor A (chunk, chunk, K) is materialized in VMEM
(chunk=32, K=64 -> 256 KiB f32), all exponents clipped <= 0 so the math is
overflow-safe (see models/rwkv.py for the derivation).

This is the TPU adaptation of the fla/CUDA chunked WKV kernels: instead of
warp-level shuffles per 16-token sub-tile, one VMEM-resident chunk per grid
step with VPU elementwise decay math and MXU matmuls for the (C,C) @ (C,V)
contraction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    f32 = jnp.float32
    rr = r_ref[0, :, 0, :].astype(f32)  # (C,K)
    kk = k_ref[0, :, 0, :].astype(f32)
    vv = v_ref[0, :, 0, :].astype(f32)  # (C,V)
    ww = w_ref[0, :, 0, :].astype(f32)
    u = u_ref[0].astype(f32)  # (K,)

    logw = -jnp.exp(ww)
    Li = jnp.cumsum(logw, axis=0)  # (C,K) inclusive
    Le = Li - logw  # exclusive
    # A[t,s,k] = exp(Le[t]-Li[s]) for s < t
    A = jnp.exp(jnp.clip(Le[:, None, :] - Li[None, :, :], -60.0, 0.0))
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) < \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    A = jnp.where(mask[:, :, None], A, 0.0)
    tmp = jnp.sum(rr[:, None, :] * A * kk[None, :, :], axis=-1)  # (C,C)
    y = jax.lax.dot_general(tmp, vv, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
    # diagonal bonus
    y += jnp.sum(rr * u[None, :] * kk, axis=-1, keepdims=True) * vv
    # incoming state
    S_in = s_scr[...]
    y += jax.lax.dot_general(rr * jnp.exp(Le), S_in, (((1,), (0,)), ((), ())),
                             preferred_element_type=f32)
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    # state update
    decay_all = jnp.exp(Li[-1])  # (K,)
    kd = kk * jnp.exp(Li[-1][None, :] - Li)  # (C,K)
    s_scr[...] = decay_all[:, None] * S_in + jax.lax.dot_general(
        kd, vv, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    @pl.when(ic == n_chunks - 1)
    def _fin():
        sout_ref[0, 0] = s_scr[...]


def wkv6_chunked(r, k, v, w, u, state, *, chunk=32, interpret=False):
    """Shapes as in ref.wkv6. Returns (y f32, state_out f32)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    assert S % chunk == 0
    n = S // chunk
    grid = (B, H, n)
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=n)
    y, sout = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, K), lambda b, h, ic: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, state)
    return y, sout
