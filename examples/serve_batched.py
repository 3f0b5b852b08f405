"""Batched serving example: prefill a batch of prompts, then run a greedy
continuous decode loop with per-step latency stats — across model families
(dense / SSM / hybrid take different cache paths through the same API).

    PYTHONPATH=src python examples/serve_batched.py --arch rwkv6-7b
    PYTHONPATH=src python examples/serve_batched.py --arch smollm-135m --gen 32
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.distributed.sharding import ShardingCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm, params as P
from repro.serve.step import make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    bundle = registry.get(args.arch)
    cfg, run = bundle.smoke, bundle.run
    ctx = ShardingCtx.null()
    rng = jax.random.PRNGKey(0)
    params = P.materialize(lm.param_specs(cfg), rng, dtype=run.compute_dtype)

    batch = {"tokens": jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.02 * jnp.ones(
            (args.batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frame_embeds"] = 0.02 * jnp.ones(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)

    prefill = jax.jit(make_prefill_step(cfg, run, ctx))
    decode = jax.jit(make_decode_step(cfg, run, ctx))

    t0 = time.time()
    tok, cache = prefill(params, batch)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    # transformer-family caches need room for generated tokens (ssm/hybrid
    # states are fixed-size; SWA ring buffers stay window-sized)
    if cfg.num_heads > 0 and cfg.sliding_window == 0 and cfg.family != "ssm":
        def pad(x):
            if x.ndim == 5 and x.shape[2] == args.prompt_len:
                return jnp.pad(x, [(0, 0), (0, 0), (0, args.gen), (0, 0), (0, 0)])
            return x
        cache = jax.tree.map(pad, cache)

    lat = []
    outs = [np.asarray(tok)]
    for i in range(args.gen - 1):
        t1 = time.time()
        tok, cache = decode(params, cache,
                            {"tokens": tok[:, None],
                             "pos": jnp.int32(args.prompt_len + i)})
        jax.block_until_ready(tok)
        lat.append(time.time() - t1)
    outs = np.stack(outs, 0)

    lat_ms = np.array(lat[1:]) * 1e3  # skip first (compile already done, warmup)
    print(f"{cfg.name}: batch={args.batch} prefill={t_prefill*1e3:.0f}ms "
          f"decode p50={np.percentile(lat_ms,50):.1f}ms "
          f"p99={np.percentile(lat_ms,99):.1f}ms/token "
          f"throughput={args.batch/np.mean(lat_ms)*1e3:,.0f} tok/s")


if __name__ == "__main__":
    main()
