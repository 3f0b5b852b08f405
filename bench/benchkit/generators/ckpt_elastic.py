"""The checkpoint cycle across a change of layout: a job saves under one
mesh, is killed, and resumes under another (ByteCheckpoint's resharding
between parallelism settings, arXiv:2407.20143).

The traffic's ``layouts`` name two meshes, ``{"data": d, "model": m}``
each, over the first ``d * m`` chips. A ``Trainer`` per layout is built
in set-up; cycle ``k`` (set-up's counted) trains and saves under
``layouts[k % 2]``, kills that job (no unmount, its state in HBM freed)
and resumes under ``layouts[(k + 1) % 2]``, into a trainer that has never
held random state. Set-up runs both directions once, so both meshes'
programs are compiled; the window's first cycle is ``layouts[0]`` to
``layouts[1]``.

End-to-end metrics, set-up, the control and the comparison are
``ckpt_cycle``'s, and the comparison adds one count: device shards that
differ from the whole-tensor reference's slice (``restore_ref``) and
restored leaves whose sharding is not the resuming layout's. It covers
the window's restores whose checkpoint is still kept (the newest
``keep``).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, List

from benchkit import restore_ref
from benchkit.faults import VolatileWrites
from benchkit.generators import ckpt_cycle
from benchkit.meter import span

ROOT_DIR = ckpt_cycle.ROOT_DIR


class Generator(ckpt_cycle.Generator):
    def __init__(self, config: Dict, traffic: Dict, **kw):
        super().__init__(config, traffic, **kw)
        self.layouts = [(int(x["data"]), int(x["model"]))
                        for x in traffic["layouts"]]
        self.trainers: List = []
        self.k = 0  # cycles run, set-up's included

    # --- set-up --------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.configs import registry
        from repro.fs.mounts import blocks_for, make_mount
        from repro.launch.mesh import make_elastic_mesh
        from repro.train.trainer import Trainer, state_nbytes

        bundle = registry.get(self.config["system"]["arch"])
        cfg = dataclasses.replace(
            bundle.model, **{repo: self.config[hf] for hf, repo
                             in ckpt_cycle.HF_TO_REPO.items()})
        state = self.config["state"]
        run = bundle.run.replace(microbatch_per_data_shard=0,
                                 param_dtype=state["param_dtype"],
                                 moment_dtype=state["moment_dtype"],
                                 compute_dtype=state["compute_dtype"])
        self.state_bytes = state_nbytes(cfg, run)
        self.mf = make_mount("bento", n_blocks=blocks_for(
            (self.keep + 1) * self.state_bytes))
        self.dev = self.mf.dev
        self.meter.attach(self.mf.services)
        job = self.config["job"]
        self.trainers = [
            Trainer(cfg, run, global_batch=job["global_batch"],
                    seq_len=job["seq_len"], seed=self.seed,
                    mesh=make_elastic_mesh(d, m), ckpt_view=self.mf.view)
            for d, m in self.layouts]
        self.trainer = self.trainers[0]
        leaves = jax.tree.leaves(self._state())
        jax.block_until_ready(leaves)
        self.data_blocks = sum(-(-x.nbytes // ckpt_cycle.BLOCK)
                               for x in leaves)
        self.n_leaves = len(leaves)
        # the window's retention makes the first deletions, whose commits
        # hash batch sizes no save uses: compile every size a commit can
        # hash (up to the journal's capacity) here
        block = bytes(ckpt_cycle.BLOCK)
        for n in range(1, self.mf.mount.module.journal.capacity + 1):
            self.mf.services.checksum_batch([block] * n)
        for _ in self.layouts:  # both directions compile here
            self._cycle()
            if self.failed:
                raise RuntimeError("a set-up cycle failed:\n"
                                   + self.errors[0])
        self.cycles.clear()
        self.attempted = 0
        self.meter.reset()
        self._lose_writes = self.control

    # --- one cycle -------------------------------------------------------------
    def _kill(self) -> None:
        if self.volatile is not None:
            self.volatile.lose()
        self.mf = None
        self.trainer.drop_state()

    def _cycle(self) -> None:
        import jax
        from repro.fs.mounts import remount

        n = len(self.layouts)
        src, dst = self.trainers[self.k % n], self.trainers[(self.k + 1) % n]
        rec = {}
        self.k += 1
        self.trainer = src
        self.attempted += 2  # a save and a resume
        try:
            with span("bench.train_steps"):
                src.train(src.step_idx + self.steps)
            step = src.step_idx
            rec["step"] = step
            with span("bench.digest"):
                self.saved[step] = self._digests()
            self.volatile = (VolatileWrites(self.dev) if self._lose_writes
                             else None)
            if self.volatile is not None:
                self.volatile.arm()
            writes0 = self.dev.writes
            self.meter.phase = "save"
            t0 = time.perf_counter()
            with span("bench.save"):
                src.save_checkpoint()
            rec["save_dev_writes"] = self.dev.writes - writes0
            with span("bench.retention"):
                self._retain(self.mf.view)
            rec["stall_s"] = time.perf_counter() - t0
            self.meter.phase = None
            with span("bench.kill"):
                self._kill()
            self.meter.phase = "resume"
            t0 = time.perf_counter()
            with span("bench.remount"):
                self.mf = remount(self.dev)
            self.meter.attach(self.mf.services)
            dst.ckpt_view = self.mf.view
            self.trainer = dst
            with span("bench.restore"):
                if not dst.restore_checkpoint():
                    raise RuntimeError("no checkpoint found at the resume")
                jax.block_until_ready(self._state())
            rec["resume_s"] = time.perf_counter() - t0
            self.meter.phase = None
            rec["fetch_s"] = dst.last_restore_stats["pipeline"]["fetch_s"]
            rec["restored_step"] = dst.step_idx
            with span("bench.digest"):
                rec["restored"] = self._digests()
                rec["placed"] = restore_ref.placement(
                    jax.tree.leaves(self._state()), self._targets(dst))
        except Exception:  # noqa: BLE001 — a failed cycle is a result
            self.meter.phase = None
            self.failed += 1
            self.errors.append(traceback.format_exc())
        self.cycles.append(rec)

    @staticmethod
    def _targets(trainer) -> List:
        """The layout's sharding of each leaf, in the saved order."""
        import jax
        return jax.tree.leaves({"params": trainer.param_shardings,
                                "opt": trainer.opt_shardings})

    def release(self) -> None:
        """Free the job's state in HBM before the reference runs."""
        for tr in self.trainers:
            tr.drop_state()
        self.trainers, self.trainer = [], None

    # --- results -------------------------------------------------------------------
    def check(self) -> List[tuple]:
        checks = super().check()
        return checks + [("resharded_wrong", self._check_resharded(), 0)]

    def _check_resharded(self) -> int:
        """Each window restore whose checkpoint is kept, against the
        whole-tensor restore read through a cold mount."""
        from repro.fs.mounts import remount

        self.mf = None
        view = remount(self.dev).view
        kept = set(sorted(self.saved)[-self.keep:])
        wrong = 0
        for c in self.cycles:
            if c.get("step") not in kept or "placed" not in c:
                continue
            d = f"{ROOT_DIR}/{ckpt_cycle._step_name(c['step'])}"
            try:
                manifest = json.loads(view.read_file(f"{d}/manifest.json"))
            except Exception:  # noqa: BLE001 — unreadable: all wrong
                wrong += self.n_leaves
                continue
            print(f"manifest {c['step']}: mesh_axes "
                  f"{manifest['extra'].get('mesh_axes')}, "
                  f"{sum(len(r['shards']) for r in manifest['leaves'])} "
                  f"shard files", file=sys.stderr)
            for rec, placed in zip(manifest["leaves"], c["placed"]):
                wrong += restore_ref.shards_wrong(
                    restore_ref.whole_leaf(view, rec), placed)
        return wrong

