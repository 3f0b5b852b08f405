#!/usr/bin/env python3
"""Proof that the main path runs on a TPU, in one process.

    python3 chip_smoke.py             # one chip: every phase below
    python3 chip_smoke.py --chips 4   # four chips: the elastic restore only

On one chip: the blockhash kernel against its host reference; a
``bento`` mount sized from the training state; SmolLM-135M at its
published widths, seeded random weights, training a few steps; a
checkpoint save with a checksum on every shard; a cold remount of the
same device; and a restore into a fresh trainer that must match the
saved state byte for byte and take the next step to the same loss.

With ``--chips 4``: train on a data=4 mesh, save the sharded state, and
restore it onto data=4, data=2 and data=1 meshes, each shard compared
with the whole-tensor reference and checked on the device it belongs to.
This phase keeps SmolLM's widths and cuts its depth to ELASTIC_LAYERS:
every leaf keeps its shape but the stacked layer axis, and the largest
leaf, the embedding, is whole, so the shards and placements are the
same kinds at a third of the bytes.

Each earlier line of output is one JSON record per phase. The last line
is ``{"ok": true, "device": {...}}``. Where JAX finds no TPU the script
exits nonzero and prints no such line; any failed check raises.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
BATCH, SEQ = 8, 512
ELASTIC_LAYERS = 4
HASH_BATCHES = (1, 7, 63, 64, 1000)
LONG_BYTES = 49152 * 576 * 4  # SmolLM-135M's largest leaf: the f32 embedding


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def emit(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}), flush=True)


def _since(t0: float) -> float:
    return time.perf_counter() - t0


def phase_blockhash(rng, *, long_bytes=LONG_BYTES, interpret=False) -> None:
    """Batches of 4 KiB blocks and one long buffer, each bit-identical to
    the host reference."""
    from repro.kernels.blockhash import ops, ref

    t0 = time.perf_counter()
    for n in HASH_BATCHES:
        blocks = [rng.bytes(ops.ROW_BYTES) for _ in range(n)]
        got = ops.checksum_batch(blocks, interpret=interpret)
        check(got == [ref.blockhash_np(b) for b in blocks],
              f"blockhash of a batch of {n} blocks")
    batches_s = _since(t0)
    buf = rng.bytes(long_bytes)
    t0 = time.perf_counter()
    got = ops.checksum(buf, interpret=interpret)
    long_s = _since(t0)
    check(got == ref.blockhash_np(buf), f"blockhash of {long_bytes} bytes")
    emit("blockhash", batches=list(HASH_BATCHES), batches_s=batches_s,
         long_bytes=long_bytes, long_s_incl_compile=long_s)


def _host_state(trainer):
    import jax
    return jax.device_get({"params": trainer.params,
                           "opt": trainer.opt_state})


def _train(trainer, steps: int):
    """Steps to ``steps``; the first one's time includes its compile."""
    t0 = time.perf_counter()
    trainer.train(trainer.step_idx + 1)
    first_s = _since(t0)
    t0 = time.perf_counter()
    trainer.train(steps)
    rest_s = _since(t0)
    losses = [m["loss"] for m in trainer.metrics_log]
    check(len(losses) == steps and all(map(math.isfinite, losses)),
          f"finite losses over {steps} steps: {losses}")
    return {"steps": steps, "losses": losses,
            "first_step_s_incl_compile": first_s, "later_steps_s": rest_s}


def phase_checkpoint_cycle(cfg, run, *, batch, seq, steps, seed,
                           impl="blockhash-pallas") -> None:
    """Train, save, cold remount, restore into a fresh trainer, compare."""
    import jax
    from repro.fs.mounts import blocks_for, make_mount, remount
    from repro.train.trainer import Trainer, state_nbytes

    t0 = time.perf_counter()
    nbytes = state_nbytes(cfg, run)
    n_blocks = blocks_for(nbytes)
    mf = make_mount("bento", n_blocks=n_blocks)
    ks = mf.services
    check(ks.checksum_impl == impl,
          f"mount bound {ks.checksum_impl}, expected {impl}")
    trainer = Trainer(cfg, run, global_batch=batch, seq_len=seq, seed=seed,
                      ckpt_view=mf.view)
    jax.block_until_ready(trainer.params)
    emit("setup", arch=cfg.name, state_bytes=nbytes, device_blocks=n_blocks,
         checksum_impl=ks.checksum_impl, seconds=_since(t0))
    emit("train", batch=batch, seq=seq, **_train(trainer, steps))

    saved = _host_state(trainer)
    calls0 = ks.counters["checksum_batch_calls"]
    t0 = time.perf_counter()
    trainer.save_checkpoint()
    save_s = _since(t0)
    check(ks.counters["checksum_batch_calls"] > calls0,
          "the save launched no checksum batch")
    emit("save", seconds=save_s, state_bytes=nbytes,
         journal_commits=mf.mount.module.journal.commits,
         checksum_impl=ks.checksum_impl, counters=dict(ks.counters))

    mf.close()  # unmounted: what follows sees only the device
    t0 = time.perf_counter()
    cold = remount(mf.dev)
    remount_s = _since(t0)
    check(cold.services.checksum_impl == impl, "remount binding")
    fresh = Trainer(cfg, run, global_batch=batch, seq_len=seq,
                    seed=seed + 1, ckpt_view=cold.view)
    t0 = time.perf_counter()
    check(fresh.restore_checkpoint(), "no checkpoint after the remount")
    jax.block_until_ready(fresh.params)
    restore_s = _since(t0)
    check(fresh.step_idx == steps, f"restored step {fresh.step_idx}")
    got = _host_state(fresh)
    want_leaves, got_leaves = jax.tree.leaves(saved), jax.tree.leaves(got)
    check(len(want_leaves) == len(got_leaves), "leaf count")
    for i, (a, b) in enumerate(zip(want_leaves, got_leaves)):
        a, b = np.asarray(a), np.asarray(b)
        check(a.dtype == b.dtype and a.shape == b.shape
              and a.tobytes() == b.tobytes(), f"restored leaf {i}")

    nxt = trainer.data.batch(trainer.step_idx)
    loss_saved = trainer.run_step(nxt)["loss"]
    loss_restored = fresh.run_step(nxt)["loss"]
    check(math.isfinite(loss_saved) and loss_saved == loss_restored,
          f"next-step loss {loss_saved} vs restored {loss_restored}")
    emit("restore", remount_s=remount_s, restore_s=restore_s,
         pipeline=fresh.last_restore_stats.get("pipeline"),
         leaves=len(got_leaves), bytes_identical=True,
         next_loss=loss_saved, next_loss_restored=loss_restored,
         counters=dict(cold.services.counters))
    cold.close()


def phase_elastic(cfg, run, *, batch, seq, steps, seed, ways=(4, 2, 1),
                  impl="blockhash-pallas") -> None:
    """Save on data=ways[0] over the real devices, restore onto each of
    ``ways``: every shard byte-identical and on its own device."""
    import jax
    from repro.fs.mounts import blocks_for, make_mount
    from repro.launch.mesh import make_elastic_mesh, make_host_mesh
    from repro.train.trainer import Trainer, state_nbytes

    nbytes = state_nbytes(cfg, run)
    mf = make_mount("bento", n_blocks=blocks_for(nbytes))
    check(mf.services.checksum_impl == impl, "mount binding")
    trainer = Trainer(cfg, run, global_batch=batch, seq_len=seq, seed=seed,
                      mesh=make_host_mesh(ways[0], 1), ckpt_view=mf.view)
    emit("train", data=ways[0], layers=cfg.num_layers, batch=batch, seq=seq,
         **_train(trainer, steps))
    saved = _host_state(trainer)
    t0 = time.perf_counter()
    trainer.save_checkpoint()
    emit("save", data=ways[0], seconds=_since(t0), state_bytes=nbytes,
         counters=dict(mf.services.counters))
    want = jax.tree.leaves(saved)
    for d in ways:
        mesh = make_elastic_mesh(d, 1)
        target = Trainer(cfg, run, global_batch=batch, seq_len=seq,
                         seed=seed + 1, mesh=mesh, ckpt_view=mf.view)
        t0 = time.perf_counter()
        check(target.restore_checkpoint(), f"restore onto data={d}")
        jax.block_until_ready(target.params)
        restore_s = _since(t0)
        leaves = jax.tree.leaves({"params": target.params,
                                  "opt": target.opt_state})
        shardings = jax.tree.leaves(target._ckpt_shardings())
        check(len(leaves) == len(want) == len(shardings), "leaf count")
        split = 0
        for i, (leaf, ref, sh) in enumerate(zip(leaves, want, shardings)):
            ref = np.asarray(ref)
            imap = sh.devices_indices_map(ref.shape)
            check(leaf.sharding.devices_indices_map(ref.shape) == imap,
                  f"data={d} leaf {i}: placement differs from its sharding")
            shards = leaf.addressable_shards
            check({s.device for s in shards} == set(mesh.devices.flat),
                  f"data={d} leaf {i}: shards not on every mesh device")
            for s in shards:
                check(s.index == imap[s.device]
                      and np.asarray(s.data).tobytes()
                      == np.ascontiguousarray(ref[s.index]).tobytes(),
                      f"data={d} leaf {i}: shard on {s.device} differs")
            split += len({str(s.index) for s in shards}) > 1
        check(d == 1 or split > 0, f"data={d}: no leaf is split")
        emit("elastic_restore", data=d, restore_s=restore_s,
             pipeline=target.last_restore_stats.get("pipeline"),
             leaves=len(leaves), split_leaves=split, bytes_identical=True)
        del target, leaves
    mf.close()


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the elastic restore across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device}", file=sys.stderr)
        return 1
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX found {len(devices)} devices")
    emit("device", **device)

    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache

    emit("compile_cache", dir=enable_compile_cache())
    bundle = registry.get(ARCH)
    run = bundle.run.replace(microbatch_per_data_shard=0)
    if args.chips == 4:
        cfg = dataclasses.replace(bundle.model, num_layers=ELASTIC_LAYERS)
        phase_elastic(cfg, run, batch=BATCH, seq=SEQ, steps=2,
                      seed=args.seed)
    else:
        phase_blockhash(np.random.default_rng(args.seed))
        phase_checkpoint_cycle(bundle.model, run, batch=BATCH, seq=SEQ,
                               steps=3, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
