"""Flash attention forward Pallas kernel (TPU).

Tiling: grid (batch, q_head, Sq/block_q, Skv/block_kv), kv innermost with
"arbitrary" semantics so the (m, l, acc) VMEM scratch carries across kv
steps — the online-softmax recurrence. The wrapper moves heads in front
of the sequence, (B, H, S, D), so each block's last two dims are
(block, D): a whole-D lane dim and a sequence sublane dim, the tiling
Mosaic requires. Batch and head are squeezed out of the block. Fully
masked causal/SWA blocks are skipped with ``pl.when`` (no MXU work issued),
so kernel FLOPs match the causal-optimal count — replacing the XLA
chunked-softmax path's ~2x causal waste on TPU.

GQA is handled by indexing the kv head as q_head // group_size.
VMEM footprint per step: q/k/v tiles (block_q + 2*block_kv) x D x 2B plus
f32 scratch (block_q x (D + 2)) — ~0.6 MiB at (256, 256, 128), far under
the ~16 MiB v5e VMEM budget, leaving room for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int, softcap: float,
                block_q: int, block_kv: int, n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    kv_start = ik * block_kv

    live = True
    if causal:
        live = kv_start <= q_start + block_q - 1
    if window > 0:
        live = live & (kv_start + block_kv - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (bq, D)
        k = k_ref[...].astype(jnp.float32)  # (bkv, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, softcap=0.0,
                        block_q=256, block_kv=256, interpret=False):
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D). Returns (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    n_q, n_kv = Sq // block_q, Skv // block_kv
    grid = (B, Hq, n_q, n_kv)
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, n_kv=n_kv)

    q_spec = pl.BlockSpec((None, None, block_q, D),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_kv, D),
                           lambda b, h, iq, ik, G=G: (b, h // G, ik, 0))
    heads_first = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
