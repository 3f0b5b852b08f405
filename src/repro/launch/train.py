"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt-every 10

Full-config production runs use the same entry point with a real TPU mesh
(jax.distributed.initialize on the pod slice); on this CPU container the
smoke configs are the runnable path.
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import registry
from repro.fs.mounts import blocks_for, make_mount
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.trainer import Trainer, state_nbytes


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-mesh", type=int, default=0,
                    help=">0: data-parallel ways over host devices")
    ap.add_argument("--ruleset", default="baseline")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args()
    enable_compile_cache()

    bundle = registry.get(args.arch)
    cfg = bundle.smoke if args.smoke else bundle.model
    run = bundle.run.replace(microbatch_per_data_shard=0)
    mesh = make_host_mesh(args.data_mesh, 1) if args.data_mesh > 1 else None

    mf = None
    ckpt_view = None
    if args.ckpt_every:
        # every save is kept: size the device for all of them
        saves = max(1, args.steps // args.ckpt_every)
        mf = make_mount("bento",
                        n_blocks=blocks_for(saves * state_nbytes(cfg, run)))
        ckpt_view = mf.view

    t = Trainer(cfg, run, global_batch=args.batch, seq_len=args.seq,
                mesh=mesh, ruleset=args.ruleset,
                ckpt_view=ckpt_view, ckpt_every=args.ckpt_every)
    t0 = time.time()
    t.train(args.steps)
    wall = time.time() - t0
    first, last = t.metrics_log[0], t.metrics_log[-1]
    print(f"arch={cfg.name} steps={args.steps} wall={wall:.1f}s "
          f"loss {first['loss']:.4f} -> {last['loss']:.4f} "
          f"({args.steps * args.batch * args.seq / wall:.0f} tok/s)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(t.metrics_log, f, indent=1)
    if mf is not None:
        mf.close()


if __name__ == "__main__":
    main()
