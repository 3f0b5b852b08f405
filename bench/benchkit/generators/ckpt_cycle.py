"""A training job that saves its state through the file system, is killed
and resumes: ByteCheckpoint's pattern (arXiv:2407.20143).

One cycle is ``steps_between_saves`` training steps (the client's load),
a save through ``Trainer.save_checkpoint`` on a ``bento`` mount, the
deletion of checkpoints older than the newest ``keep`` (made durable with
an fsync), a kill that drops the mount without an unmount and frees the
state in HBM, and a resume: a cold ``remount`` of the same device
(journal recovery included) and ``Trainer.restore_checkpoint`` until the
state is back in HBM.

End-to-end metrics: ``save_stall_s``, the seconds the step loop is
blocked per save (save plus retention), and ``resume_s``, the seconds
from the kill to the restored state. Set-up runs one whole cycle, so
every program the window runs is compiled before it starts.

``correct`` holds each restore, leaf by leaf, against digests of the
state the job saved, and after the window reads back from the device the
namespace of every kept checkpoint and, of each kept one the window
saved, its manifest, each shard's payload against the saved digests, and
each stored checksum against the host reference hash.
"""

from __future__ import annotations

import dataclasses
import io
import json
import time
import traceback
from typing import Dict, List

import numpy as np

from benchkit import refs
from benchkit.faults import VolatileWrites
from benchkit.meter import span

# keys of the model's published config.json -> the program's ModelConfig
HF_TO_REPO = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"}
ROOT_DIR = "/ckpt"
BLOCK = 4096


def _step_name(step: int) -> str:
    return f"step_{step:08d}"


class Generator:
    def __init__(self, config: Dict, traffic: Dict, *, seed: int, meter,
                 control: bool = False):
        self.config, self.traffic = config, traffic
        self.seed = int(np.random.SeedSequence(seed).generate_state(1)[0]
                        & 0x7FFFFFFF)
        self.meter = meter
        self.control = control
        self.steps = int(traffic["steps_between_saves"])
        self.keep = int(traffic["keep"])
        self.cycles: List[Dict] = []
        self.saved: Dict[int, List[str]] = {}  # step -> leaf digests
        self.errors: List[str] = []
        self.attempted = 0
        self.failed = 0
        self.mf = None
        self.dev = None
        self.volatile = None
        self._lose_writes = False  # the control's volatile device

    # --- set-up --------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.configs import registry
        from repro.fs.mounts import blocks_for, make_mount
        from repro.train.trainer import Trainer, state_nbytes

        bundle = registry.get(self.config["system"]["arch"])
        cfg = dataclasses.replace(
            bundle.model, **{repo: self.config[hf]
                             for hf, repo in HF_TO_REPO.items()})
        state = self.config["state"]
        run = bundle.run.replace(microbatch_per_data_shard=0,
                                 param_dtype=state["param_dtype"],
                                 moment_dtype=state["moment_dtype"],
                                 compute_dtype=state["compute_dtype"])
        self.state_bytes = state_nbytes(cfg, run)
        # the kept checkpoints plus the one being written
        self.mf = make_mount("bento", n_blocks=blocks_for(
            (self.keep + 1) * self.state_bytes))
        self.dev = self.mf.dev
        self.meter.attach(self.mf.services)
        job = self.config["job"]
        self.trainer = Trainer(cfg, run, global_batch=job["global_batch"],
                               seq_len=job["seq_len"], seed=self.seed,
                               ckpt_view=self.mf.view)
        leaves = jax.tree.leaves(self._state())
        jax.block_until_ready(leaves)
        self.data_blocks = sum(-(-x.nbytes // BLOCK) for x in leaves)
        self.n_leaves = len(leaves)
        # one whole cycle: every shape the window uses compiles here
        self._cycle()
        if self.failed:
            raise RuntimeError("the set-up cycle failed:\n" + self.errors[0])
        self.cycles.clear()
        self.attempted = 0
        self.meter.reset()
        self._lose_writes = self.control

    # --- one cycle -------------------------------------------------------------
    def _state(self):
        """The state as the trainer saves it (its leaves in that order)."""
        return {"params": self.trainer.params, "opt": self.trainer.opt_state}

    def _digests(self) -> List[str]:
        import jax
        return refs.leaf_digests(jax.tree.leaves(jax.device_get(
            self._state())))

    def _retain(self, view) -> None:
        names = sorted(n for n in view.listdir(ROOT_DIR)
                       if n.startswith("step_"))
        for name in names[:-self.keep]:
            d = f"{ROOT_DIR}/{name}"
            files = [f for f in view.listdir(d) if f not in (".", "..")]
            if files:
                view.unlink_many([f"{d}/{f}" for f in files])
            view.rmdir(d)
        view.fsync(ROOT_DIR)

    def _kill(self) -> None:
        """Drop the mount without an unmount and free the state in HBM:
        what is not on the device is gone."""
        import jax

        if self.volatile is not None:
            self.volatile.lose()
        self.mf = None
        tr = self.trainer
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            self._state())
        for x in jax.tree.leaves(self._state()):
            x.delete()
        tr.params, tr.opt_state = like["params"], like["opt"]

    def _cycle(self) -> None:
        import jax
        from repro.fs.mounts import remount

        tr, rec = self.trainer, {}
        self.attempted += 2  # a save and a resume
        try:
            with span("bench.train_steps"):
                tr.train(tr.step_idx + self.steps)
            step = tr.step_idx
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
                tr.save_checkpoint()
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
            tr.ckpt_view = self.mf.view
            with span("bench.restore"):
                if not tr.restore_checkpoint():
                    raise RuntimeError("no checkpoint found at the resume")
                jax.block_until_ready(self._state())
            rec["resume_s"] = time.perf_counter() - t0
            self.meter.phase = None
            rec["fetch_s"] = tr.last_restore_stats["pipeline"]["fetch_s"]
            rec["restored_step"] = tr.step_idx
            with span("bench.digest"):
                rec["restored"] = self._digests()
        except Exception:  # noqa: BLE001 — a failed cycle is a result
            self.meter.phase = None
            self.failed += 1
            self.errors.append(traceback.format_exc())
        self.cycles.append(rec)

    # --- the window --------------------------------------------------------------
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds and not self.failed:
            self._cycle()

    def release(self) -> None:
        """Free the job's state in HBM before the reference runs."""
        import jax
        for x in jax.tree.leaves(self._state()):
            if isinstance(x, jax.Array):
                x.delete()
        self.trainer = None

    # --- results -------------------------------------------------------------------
    def _done(self, key: str) -> List[float]:
        return [c[key] for c in self.cycles if key in c]

    def end_to_end(self) -> Dict[str, float]:
        out = {}
        stalls, resumes = self._done("stall_s"), self._done("resume_s")
        if stalls:
            out["save_stall_s"] = sum(stalls) / len(stalls)
        if resumes:
            out["resume_s"] = sum(resumes) / len(resumes)
        return out

    def record(self) -> Dict:
        saves = self._done("save_dev_writes")
        return {
            "hash": dict(self.meter.totals),
            "samples": {"restore_fetch_s": self._done("fetch_s")},
            "counters": {"save": {
                "dev_writes": sum(saves),
                "data_blocks": self.data_blocks * len(saves)}},
        }

    def check(self) -> List[tuple]:
        """Numbers compared, each with its limit; all are exact counts."""
        leaves_wrong = 0
        for c in self.cycles:
            want = self.saved.get(c.get("step"))
            got = c.get("restored")
            if got is None or want is None \
                    or c.get("restored_step") != c.get("step"):
                leaves_wrong += self.n_leaves
            else:
                leaves_wrong += sum(a != b for a, b in zip(want, got))
        names_wrong, stored_wrong, sums_wrong = self._check_device()
        return [("ops_failed", self.failed, 0),
                ("restored_leaves_wrong", leaves_wrong, 0),
                ("names_wrong", names_wrong, 0),
                ("stored_leaves_wrong", stored_wrong, 0),
                ("checksums_wrong", sums_wrong, 0)]

    def _check_device(self):
        """Read the kept checkpoints back from the device through a cold
        mount: namespace, manifests, payloads and stored checksums."""
        from repro.fs.mounts import remount

        self.mf = None
        mf = remount(self.dev)
        view = mf.view
        # the hash the binding states for the on-disk format
        stored_hash = (refs.blockhash
                       if mf.services.checksum_impl.startswith("blockhash")
                       else refs.crc32)
        kept = sorted(self.saved)[-self.keep:]
        want_names = {_step_name(s) for s in kept}
        try:
            have = {n for n in view.listdir(ROOT_DIR)
                    if n not in (".", "..")}
        except Exception:  # noqa: BLE001 — a missing root is a wrong name
            have = set()
        names_wrong = len(want_names ^ have)
        stored_wrong = sums_wrong = 0
        # the answers due in the window: the kept checkpoints it saved
        window_steps = {c.get("step") for c in self.cycles}
        for step in (s for s in kept if s in window_steps):
            d = f"{ROOT_DIR}/{_step_name(step)}"
            try:
                manifest = json.loads(view.read_file(f"{d}/manifest.json"))
                files = {f for f in view.listdir(d) if f not in (".", "..")}
            except Exception:  # noqa: BLE001 — unreadable: all wrong
                stored_wrong += self.n_leaves
                continue
            shard_files = {s["path"].rsplit("/", 1)[-1]
                           for r in manifest["leaves"] for s in r["shards"]}
            names_wrong += len(files ^ (shard_files | {"manifest.json"}))
            if manifest.get("step") != step \
                    or len(manifest["leaves"]) != self.n_leaves:
                stored_wrong += self.n_leaves
                continue
            want = self.saved[step]
            for i, r in enumerate(manifest["leaves"]):
                ok = True
                for s in r["shards"]:
                    try:
                        raw = view.read_file(s["path"])
                    except Exception:  # noqa: BLE001
                        ok = False
                        sums_wrong += 1
                        continue
                    if stored_hash(raw) != s["checksum"]:
                        sums_wrong += 1
                    if len(r["shards"]) == 1:
                        ok &= self._payload_digest(raw, r) == want[i]
                stored_wrong += not ok
        return names_wrong, stored_wrong, sums_wrong

    @staticmethod
    def _payload_digest(raw: bytes, rec: Dict) -> str:
        arr = np.load(io.BytesIO(raw))
        if str(arr.dtype) != rec["dtype"]:
            import ml_dtypes
            arr = arr.view(np.dtype(getattr(ml_dtypes, rec["dtype"])))
        return refs.leaf_digests([arr])[0]

    def diagnostics(self) -> List[str]:
        return self.errors
