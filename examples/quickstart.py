"""Quickstart: the whole system in ~60 lines.

Mount a journaled Bento file system, train a small LM whose checkpoints
flow through it, hot-upgrade the file system mid-run (paper §4.8), and
serve a few greedy tokens from the trained weights.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core.upgrade import upgrade
from repro.distributed.sharding import ShardingCtx
from repro.fs.ext4like import Ext4LikeFileSystem
from repro.fs.mounts import make_mount
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.trainer import Trainer


def main():
    enable_compile_cache()
    bundle = registry.get("smollm-135m")
    cfg = bundle.smoke  # reduced config: runs on CPU in seconds
    run = bundle.run.replace(microbatch_per_data_shard=0, learning_rate=1e-3)

    # 1. storage: journaled xv6 behind the Bento typed boundary
    mf = make_mount("bento", n_blocks=32768)
    print(f"mounted {mf.mount.name} (generation {mf.mount.generation})")

    # 2. train with checkpoints through the fs
    t = Trainer(cfg, run, global_batch=8, seq_len=64,
                ckpt_view=mf.view, ckpt_every=5, seed=0)
    t.train(15)
    losses = [m["loss"] for m in t.metrics_log]
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps")
    print("checkpoints:", mf.view.listdir("/ckpt"))

    # 3. hot-upgrade the mounted fs (xv6 -> ext4like) without unmounting
    stats = upgrade(mf.mount, Ext4LikeFileSystem(),
                    migrate=lambda s, o, n: {**s, "dirindex": {}})
    print(f"online upgrade: {stats['total_s']*1e3:.1f} ms pause, "
          f"generation {mf.mount.generation}")
    assert mf.view.listdir("/ckpt")  # data survives

    # 4. serve greedily from the trained weights
    ctx = ShardingCtx.null()
    prefill = jax.jit(make_prefill_step(cfg, run, ctx))
    decode = jax.jit(make_decode_step(cfg, run, ctx))
    prompt = jnp.ones((1, 16), jnp.int32)
    tok, cache = prefill(t.params, {"tokens": prompt})
    cache = jax.tree.map(
        lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, 8), (0, 0), (0, 0)])
        if x.ndim == 5 else x, cache)
    out = [int(tok[0])]
    for i in range(7):
        tok, cache = decode(t.params, cache,
                            {"tokens": tok[:, None], "pos": jnp.int32(16 + i)})
        out.append(int(tok[0]))
    print("generated:", out)
    mf.close()
    print("OK")


if __name__ == "__main__":
    main()
