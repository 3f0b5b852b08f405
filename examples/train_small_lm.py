"""End-to-end training driver: train a ~100M-class model (SmolLM-135M smoke
or full config) for a few hundred steps with FS-backed data shards, failure
injection, checkpoint/restart, and deterministic resume.

    PYTHONPATH=src python examples/train_small_lm.py --steps 200
    PYTHONPATH=src python examples/train_small_lm.py --steps 50 --full  # real 135M
"""

import argparse
import time

from repro.configs import registry
from repro.data.pipeline import FsShardReader, SyntheticLM, write_shards
from repro.fs.mounts import blocks_for, make_mount
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import Trainer, WorkerFailure, state_nbytes


class FsDataset:
    """Adapter: serve training batches from Bento-FS shards."""

    def __init__(self, view, cfg, global_batch, seq_len, n_shards=8):
        base = SyntheticLM(cfg, global_batch, seq_len, seed=1234)
        write_shards(view, base, n_shards=n_shards)
        self.reader = FsShardReader(view)

    def batch(self, step: int):
        return self.reader.read(step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="train the real 135M config (slow on CPU)")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a node failure at this step")
    args = ap.parse_args()
    enable_compile_cache()

    bundle = registry.get("smollm-135m")
    cfg = bundle.model if args.full else bundle.smoke
    run = bundle.run.replace(microbatch_per_data_shard=0, learning_rate=6e-4)

    ckpt_every = max(args.steps // 10, 1)
    # every save is kept: size the device for all of them
    saves = args.steps // ckpt_every
    mf = make_mount("bento",
                    n_blocks=blocks_for(saves * state_nbytes(cfg, run)))
    data = FsDataset(mf.view, cfg, args.batch, args.seq)

    armed = {"on": args.fail_at >= 0}

    def failure_hook(step):
        if armed["on"] and step == args.fail_at:
            armed["on"] = False
            raise WorkerFailure(f"injected node loss at step {step}")

    t = Trainer(cfg, run, global_batch=args.batch, seq_len=args.seq,
                ckpt_view=mf.view, ckpt_every=ckpt_every,
                failure_hook=failure_hook if args.fail_at >= 0 else None,
                data=data)
    t0 = time.time()
    t.train(args.steps)
    wall = time.time() - t0
    ls = [m["loss"] for m in t.metrics_log]
    toks = args.steps * args.batch * args.seq
    print(f"{cfg.name}: {args.steps} steps in {wall:.1f}s "
          f"({toks/wall:,.0f} tok/s 1xCPU) loss {ls[0]:.3f} -> {ls[-1]:.3f} "
          f"recoveries={t.recoveries} shard_retries={data.reader.retries}")
    assert ls[-1] < ls[0], "training must reduce loss"
    mf.close()


if __name__ == "__main__":
    main()
