"""Serving launcher: batched prefill + decode loop (greedy).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import ShapeSpec
from repro.distributed.sharding import ShardingCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm, params as P
from repro.serve.step import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    bundle = registry.get(args.arch)
    cfg = bundle.smoke if args.smoke else bundle.model
    run = bundle.run
    ctx = ShardingCtx.null()

    rng = jax.random.PRNGKey(0)
    prm = P.materialize(lm.param_specs(cfg), rng, dtype=run.compute_dtype)
    max_len = args.prompt_len + args.gen

    batch = {"tokens": jax.random.randint(rng, (args.batch, args.prompt_len),
                                          0, cfg.vocab_size, jnp.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.02 * jnp.ones(
            (args.batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frame_embeds"] = 0.02 * jnp.ones(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)

    # prefill emits a cache sized for the prompt; decode needs room for
    # generation -> pad the prompt-time cache up to max_len.
    prefill = jax.jit(make_prefill_step(cfg, run, ctx))
    decode = jax.jit(make_decode_step(cfg, run, ctx))

    t0 = time.time()
    tok, cache = prefill(prm, batch)

    def pad_seq(x):  # (..., S, H, D) -> room for generated tokens
        padw = [(0, 0)] * x.ndim
        padw[-3] = (0, args.gen)
        return jnp.pad(x, padw)

    ring = cfg.sliding_window > 0  # SWA ring buffer keeps its window size
    if not ring:
        if cfg.family in ("dense", "moe"):
            cache = {"k": pad_seq(cache["k"]), "v": pad_seq(cache["v"])}
        elif cfg.family == "vlm":
            cache = {"self": {"k": pad_seq(cache["self"]["k"]),
                              "v": pad_seq(cache["self"]["v"])},
                     "cross": cache["cross"]}
        elif cfg.family == "audio":
            cache = {"k": pad_seq(cache["k"]), "v": pad_seq(cache["v"]),
                     "ck": cache["ck"], "cv": cache["cv"]}
        elif cfg.family == "hybrid" and "attn" in cache:
            cache = {"mamba": cache["mamba"],
                     "attn": {"k": pad_seq(cache["attn"]["k"]),
                              "v": pad_seq(cache["attn"]["v"])}}
    t_prefill = time.time() - t0

    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + i)
        tok, cache = decode(prm, cache, {"tokens": tok[:, None], "pos": pos})
        out_tokens.append(np.asarray(tok))
    t_decode = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prefill={t_prefill*1e3:.0f}ms "
          f"decode={t_decode/max(args.gen-1,1)*1e3:.1f}ms/tok")
    print("generated token ids (first row):", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
