"""Optimizer + compression unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig
from repro.distributed import compression as C
from repro.models import params as P
from repro.optim.adamw import (OptState, adamw_init_specs, adamw_update,
                               cosine_schedule)


def _setup(run: RunConfig, shape=(8, 8)):
    specs = {"w": P.dense(shape, (None, None)),
             "b": P.dense((shape[1],), (None,), init="zeros")}
    params = P.materialize(specs, jax.random.PRNGKey(0),
                           dtype=run.param_dtype)
    opt = P.materialize(adamw_init_specs(specs, run), jax.random.PRNGKey(1),
                        dtype="float32")
    return specs, params, opt


def test_adamw_minimizes_quadratic():
    run = RunConfig(learning_rate=0.05, weight_decay=0.0, grad_clip=0.0)
    _, params, opt = _setup(run)
    target = jax.random.normal(jax.random.PRNGKey(2), (8, 8))

    def loss_fn(p):
        return jnp.mean((p["w"] - target) ** 2) + jnp.mean(p["b"] ** 2)

    l0 = float(loss_fn(params))
    for _ in range(200):
        g = jax.grad(loss_fn)(params)
        params, opt, _ = adamw_update(g, params, opt, run)
    assert float(loss_fn(params)) < 0.01 * l0


def test_factored_second_moment_shapes():
    run = RunConfig(factored_second_moment=True)
    specs, params, opt = _setup(run, shape=(16, 32))
    nu_w = opt.nu["w"]
    assert set(nu_w) == {"_factored_row", "_factored_col"}
    assert nu_w["_factored_row"].shape == (16,)
    assert nu_w["_factored_col"].shape == (32,)
    g = jax.tree.map(jnp.ones_like, params)
    p2, o2, _ = adamw_update(g, params, opt, run)
    assert o2.nu["w"]["_factored_row"].shape == (16,)
    assert bool(jnp.all(jnp.isfinite(p2["w"])))


def test_master_weights_roundtrip():
    run = RunConfig(param_dtype="bfloat16", master_weights=True,
                    learning_rate=0.05, weight_decay=0.0)
    specs, params, opt = _setup(run)
    assert opt.master is not None
    assert opt.master["w"].dtype == jnp.float32
    assert params["w"].dtype == jnp.bfloat16
    # master must track updates at fp32 precision; params = cast(master)
    opt = OptState(opt.step, opt.mu, opt.nu,
                   jax.tree.map(lambda p: p.astype(jnp.float32), params))
    g = jax.tree.map(lambda p: 1e-3 * jnp.ones_like(p, jnp.float32), params)
    p2, o2, _ = adamw_update(g, params, opt, run)
    np.testing.assert_array_equal(
        np.asarray(p2["w"]), np.asarray(o2.master["w"].astype(jnp.bfloat16)))


def test_grad_clip_and_schedule():
    run = RunConfig(grad_clip=1.0)
    _, params, opt = _setup(run)
    g = jax.tree.map(lambda p: 1e6 * jnp.ones_like(p), params)
    _, _, stats = adamw_update(g, params, opt, run)
    assert float(stats["grad_norm"]) > 1e6  # reported pre-clip
    lr0 = cosine_schedule(jnp.int32(0), 1e-3)
    lr_mid = cosine_schedule(jnp.int32(200), 1e-3)
    lr_end = cosine_schedule(jnp.int32(10_000), 1e-3)
    assert float(lr0) < float(lr_mid)
    assert float(lr_end) < 1e-6 + 0.0 * float(lr_mid)


# --- compression -----------------------------------------------------------------


def test_int8_ef_reduces_bias_over_steps():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 0.01
    r = jnp.zeros_like(x)
    # with error feedback, accumulated quantized sum converges to true sum
    acc_q = jnp.zeros_like(x)
    for _ in range(50):
        q, s, r = C.ef_compress_int8(x, r)
        acc_q += C.dequantize_int8(q, s)
    true = 50 * x
    rel = float(jnp.linalg.norm(acc_q - true) / jnp.linalg.norm(true))
    assert rel < 0.02, rel


def test_topk_ef_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(1), (128,))
    r = jnp.zeros_like(x)
    payload, r2 = C.ef_compress_topk(x, r, k_frac=0.1)
    dense = C.decompress_topk(payload, x.shape)
    # residual + decompressed == original
    np.testing.assert_allclose(np.asarray(dense + r2), np.asarray(x), atol=1e-6)


def test_compressed_psum_int8_single_shard():
    from jax.sharding import PartitionSpec as PS
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (64,))
    f = jax.shard_map(lambda v: C.compressed_psum_int8(v, "data"), mesh=mesh,
                      in_specs=PS(), out_specs=PS(), check_vma=False)
    y = f(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.02)


def test_tree_compression_roundtrip():
    tree = {"a": jax.random.normal(jax.random.PRNGKey(3), (32,)),
            "b": {"c": jax.random.normal(jax.random.PRNGKey(4), (8, 8))}}
    res = C.init_residuals(tree)
    qs, scales, res2 = C.compress_tree_int8(tree, res)
    back = C.decompress_tree_int8(qs, scales)
    err = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(x - y))), tree, back)
    assert max(jax.tree.leaves(err)) < 0.05
