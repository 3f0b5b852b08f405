"""GQA attention: train (dense + chunked online-softmax), decode (KV cache,
ring-buffer SWA, shard_map flash-decoding), and cross-attention.

The chunked path is the pure-JAX flash attention used for large lowerings
(bounded temp memory); the Pallas kernel in ``repro.kernels.flash_attention``
is the TPU fast path with the same oracle semantics.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingCtx
from repro.models import params as P
from repro.models.common import apply_rope, matmul

NEG_INF = -1e30


# --- parameter specs -----------------------------------------------------------


def attn_specs(cfg: ModelConfig, *, cross: bool = False) -> Dict[str, P.TensorSpec]:
    d = cfg.d_model
    specs = {
        "wq": P.dense((d, cfg.q_dim), ("fsdp", "heads")),
        "wk": P.dense((d, cfg.kv_dim), ("fsdp", "kv_heads")),
        "wv": P.dense((d, cfg.kv_dim), ("fsdp", "kv_heads")),
        "wo": P.dense((cfg.q_dim, d), ("heads", "fsdp")),
    }
    if cfg.qkv_bias and not cross:
        specs["bq"] = P.dense((cfg.q_dim,), ("heads",), init="zeros")
        specs["bk"] = P.dense((cfg.kv_dim,), ("kv_heads",), init="zeros")
        specs["bv"] = P.dense((cfg.kv_dim,), ("kv_heads",), init="zeros")
    return specs


def project_q(cfg: ModelConfig, w, x, positions, ctx: ShardingCtx, *, rope=True):
    dt = x.dtype
    q = matmul(x, w["wq"])
    if "bq" in w:
        q = q + w["bq"].astype(dt)
    B, S = x.shape[:2]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
    return ctx.constrain(q, ("batch", "seq_inner", "heads", "head_dim"))


def project_kv(cfg: ModelConfig, w, x, positions, ctx: ShardingCtx, *, rope=True):
    dt = x.dtype
    k = matmul(x, w["wk"])
    v = matmul(x, w["wv"])
    if "bk" in w:
        k = k + w["bk"].astype(dt)
        v = v + w["bv"].astype(dt)
    B, S = x.shape[:2]
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    k = ctx.constrain(k, ("batch", "seq_inner", "kv_heads", "head_dim"))
    v = ctx.constrain(v, ("batch", "seq_inner", "kv_heads", "head_dim"))
    return k, v


# --- core attention math ---------------------------------------------------------


def _split_groups(q: jax.Array, num_kv: int) -> jax.Array:
    """(B,S,Hq,D) -> (B,S,Hkv,G,D)."""
    B, S, Hq, D = q.shape
    return q.reshape(B, S, num_kv, Hq // num_kv, D)


def _mask(sq: int, skv: int, q_offset, *, causal: bool, window: int) -> jax.Array:
    """(sq, skv) boolean mask of allowed positions."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    m = jnp.ones((sq, skv), bool)
    if causal:
        m &= kpos <= qpos
    if window > 0:
        m &= kpos > (qpos - window)
    return m


def attention_dense(q, k, v, *, causal=True, window=0, softcap=0.0, q_offset=0):
    """Reference full-materialization GQA attention. q:(B,Sq,Hq,D) k/v:(B,Skv,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    qg = _split_groups(q, Hkv)  # (B,Sq,Hkv,G,D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(D).astype(jnp.float32)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    m = _mask(Sq, k.shape[1], q_offset, causal=causal, window=window)
    scores = jnp.where(m[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, Sq, Hq, D)


def attention_chunked(q, k, v, *, causal=True, window=0, softcap=0.0,
                      q_chunk=1024, ctx: Optional[ShardingCtx] = None):
    """Online-softmax attention, scanning over query chunks.

    Temp memory is O(q_chunk x Skv) instead of O(Sq x Skv). For SWA the kv
    range per chunk is statically sliced to [chunk_start - window, chunk_end],
    so HLO FLOPs scale with the window, not the full sequence.
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    if Sq % q_chunk != 0:
        return attention_dense(q, k, v, causal=causal, window=window, softcap=softcap)
    n_chunks = Sq // q_chunk
    qg = _split_groups(q, Hkv).reshape(B, n_chunks, q_chunk, Hkv, Hq // Hkv, D)
    qg = jnp.moveaxis(qg, 1, 0)  # (n_chunks, B, qc, Hkv, G, D)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    # Static kv slicing for SWA: chunk i sees kv [max(0, i*qc + qc - window - qc), ...]
    use_window_slice = causal and window > 0 and window % q_chunk == 0

    def one_chunk(i, qc_block):
        if use_window_slice:
            span = window + q_chunk
            start = jnp.maximum(i * q_chunk + q_chunk - span, 0)
            kc = jax.lax.dynamic_slice_in_dim(k, start, min(span, k.shape[1]), axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, min(span, k.shape[1]), axis=1)
            kv_off = start
        else:
            kc, vc, kv_off = k, v, 0
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc_block, kc).astype(jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = jnp.arange(q_chunk)[:, None] + i * q_chunk
        kpos = jnp.arange(kc.shape[1])[None, :] + kv_off
        m = jnp.ones(s.shape[-2:], bool)
        if causal:
            m &= kpos <= qpos
        if window > 0:
            m &= kpos > (qpos - window)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vc)
        return o

    def body(carry, inp):
        i, qc = inp
        return carry, one_chunk(i, qc)

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qg))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hq, D)
    return out


def _flash_blocks(n: int) -> int:
    for b in (512, 256, 128):
        if n % b == 0:
            return b
    return 0


def attention_auto(q, k, v, *, causal=True, window=0, softcap=0.0, q_chunk=1024,
                   ctx: Optional[ShardingCtx] = None):
    """Backend dispatch: Pallas flash kernel on TPU (or forced interpret via
    REPRO_ATTN=pallas_interpret for integration tests); otherwise the pure-
    jnp paths — chunked online-softmax at/beyond 2k tokens (bounds the
    scores temp at q_chunk x Skv), dense below."""
    import os
    force = os.environ.get("REPRO_ATTN", "")
    on_tpu = jax.default_backend() == "tpu"
    if (on_tpu or force == "pallas_interpret") and force != "ref":
        bq, bk = _flash_blocks(q.shape[1]), _flash_blocks(k.shape[1])
        if bq and bk:
            from repro.kernels.flash_attention import ops as fa

            def kernel(q, k, v):
                return fa.flash_attention(q, k, v, causal, window, softcap,
                                          not on_tpu)

            if ctx is None or ctx.mesh is None:
                return kernel(q, k, v)
            # a Mosaic kernel cannot be partitioned by XLA: run it per
            # batch shard, every head of that shard on its own device
            spec = ctx.spec(("batch",), q.shape[:1])
            return jax.shard_map(kernel, mesh=ctx.mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)(q, k, v)
    if q.shape[1] >= 2048 and q.shape[1] % q_chunk == 0:
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_chunk=q_chunk, ctx=ctx)
    return attention_dense(q, k, v, causal=causal, window=window, softcap=softcap)


# --- KV cache / decode -------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, P.TensorSpec]:
    shp = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    logical = ("cache_batch", "cache_seq", "cache_heads", "head_dim")
    return {
        "k": P.dense(shp, logical, init="zeros", dtype="bfloat16"),
        "v": P.dense(shp, logical, init="zeros", dtype="bfloat16"),
    }


def effective_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    # SWA caches are always window-sized ring buffers (decode continues past
    # the prefill length; index = pos %% window).
    if cfg.sliding_window > 0:
        return cfg.sliding_window
    return seq_len


def ring_layout(kv: jax.Array, window: int) -> jax.Array:
    """(B, S, H, D) full-prefill kv -> (B, window, H, D) ring-buffer layout
    where position p sits at index p %% window (zero-padded when S < window)."""
    S = kv.shape[1]
    if window <= 0:
        return kv
    if S < window:
        pad = [(0, 0)] * kv.ndim
        pad[1] = (0, window - S)
        return jnp.pad(kv, pad)
    tail = kv[:, -window:]
    return jnp.roll(tail, shift=S % window, axis=1)


def cache_update(cache_k, cache_v, k_new, v_new, pos, *, window=0):
    """Insert one token at pos (ring-buffer for SWA). k_new: (B,1,Hkv,D)."""
    cache_len = cache_k.shape[1]
    idx = jnp.where(window > 0, pos % cache_len, pos).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), idx, axis=1)
    return ck, cv


def decode_attention(q, cache_k, cache_v, pos, *, window=0, softcap=0.0):
    """One-token attention against the cache. q: (B,1,Hq,D)."""
    B, _, Hq, D = q.shape
    Hkv = cache_k.shape[2]
    S = cache_k.shape[1]
    qg = _split_groups(q, Hkv)[:, 0]  # (B,Hkv,G,D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, cache_k.astype(q.dtype)).astype(jnp.float32)
    s = s / jnp.sqrt(D).astype(jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(S)
    if window > 0:
        valid = kpos < jnp.minimum(pos + 1, S)  # ring buffer: all slots valid once full
    else:
        valid = kpos <= pos
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, cache_v.astype(q.dtype))
    return out.reshape(B, 1, Hq, D)


def flash_decode(q, cache_k, cache_v, pos, mesh, *, axis="model", softcap=0.0,
                 window=0, q_replicated=True):
    """Sequence-sharded decode attention (flash-decoding on TPU).

    The KV cache is batch-sharded over data and seq-sharded over ``axis``;
    each shard computes a partial (out, lse) and the results combine with
    the log-sum-exp trick via psum — one small collective instead of
    gathering the cache.

    ``q_replicated=True`` (the decode_flash ruleset): single-token
    activations are replicated over the data axis, so each shard slices the
    batch rows matching its cache shard, attends locally, and a tiny
    all_gather re-replicates the output.
    """
    B, _, Hq, D = q.shape
    S = cache_k.shape[1]
    n_shards = mesh.devices.shape[list(mesh.axis_names).index(axis)]
    shard_len = S // n_shards
    Hkv = cache_k.shape[2]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def per_shard(q_, ck_, cv_, pos_):
        if q_replicated and batch_axes:
            b_loc = ck_.shape[0]
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            bidx = jax.lax.axis_index(batch_axes[0])
            for a in batch_axes[1:]:  # row-major over the joint batch axes
                bidx = bidx * sizes[a] + jax.lax.axis_index(a)
            q_ = jax.lax.dynamic_slice_in_dim(q_, bidx * b_loc, b_loc, axis=0)
        B_loc, _, Hq_, D_ = q_.shape  # per-shard shapes (batch is sharded)
        shard_id = jax.lax.axis_index(axis)
        base = shard_id * shard_len
        qg = _split_groups(q_, Hkv)[:, 0]
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, ck_.astype(q_.dtype)).astype(jnp.float32)
        s = s / jnp.sqrt(D).astype(jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = jnp.arange(shard_len) + base
        if window > 0:
            valid = jnp.arange(shard_len) + base < jnp.minimum(pos_[0] + 1, S)
        else:
            valid = kpos <= pos_[0]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        # guard all-masked shards
        m_safe = jnp.maximum(m, NEG_INF / 2)
        e = jnp.exp(s - m_safe)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhgk,bkhd->bhgd", e.astype(q_.dtype), cv_.astype(q_.dtype))
        # LSE-combine across shards.
        lse = m_safe[..., 0] + jnp.log(jnp.maximum(denom[..., 0], 1e-30))
        g_max = jax.lax.pmax(lse, axis)
        w = jnp.exp(lse - g_max)  # (B,Hkv,G)
        o = o * (w / jnp.maximum(denom[..., 0], 1e-30))[..., None].astype(q_.dtype)
        o = jax.lax.psum(o.astype(jnp.float32), axis)
        z = jax.lax.psum(w, axis)
        o = (o / z[..., None]).astype(q_.dtype)
        o = o.reshape(B_loc, 1, Hq_, D_)
        if q_replicated and batch_axes:
            for a in reversed(batch_axes):  # tiny: (B,1,Hq,D) bf16
                o = jax.lax.all_gather(o, a, axis=0, tiled=True)
        return o

    spec_q = PS(None) if q_replicated or not batch_axes else PS(batch_axes)
    spec_kv = PS(batch_axes if batch_axes else None, axis)
    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv, PS()),
        out_specs=spec_q,
        check_vma=False,
    )
    return fn(q, cache_k, cache_v, jnp.broadcast_to(pos, (1,)))


# --- cross attention ------------------------------------------------------------


def cross_attention(cfg: ModelConfig, w, x, enc, ctx: ShardingCtx):
    """q from x (B,S,d); kv from enc (B,T,d). No causal mask, no rope."""
    dt = x.dtype
    B, S = x.shape[:2]
    T = enc.shape[1]
    q = matmul(x, w["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = matmul(enc, w["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = matmul(enc, w["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    q = ctx.constrain(q, ("batch", "seq", "heads", "head_dim"))
    out = attention_dense(q, k, v, causal=False)
    return matmul(out.reshape(B, S, cfg.q_dim), w["wo"])


def cross_decode(cfg: ModelConfig, w, x, ck, cv):
    """Decode-time cross attention against precomputed encoder KV."""
    dt = x.dtype
    B = x.shape[0]
    q = matmul(x, w["wq"]).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    out = attention_dense(q, ck.astype(dt), cv.astype(dt), causal=False)
    return matmul(out.reshape(B, 1, cfg.q_dim), w["wo"])
