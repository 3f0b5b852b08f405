"""Trainer supervisor: the Bento upgrade protocol applied to training.

One quiesce->extract->restore protocol (core.upgrade) gives four
fault-tolerance features:

  * checkpoint/restart  — extract -> serialize through the Bento FS,
  * failure recovery    — supervisor catches worker failures (injected in
                          tests via ``failure_hook``), restores the last
                          checkpoint and replays deterministically,
  * elastic rescale     — extract -> re-jit for a new mesh -> device_put
                          with the new shardings -> resume,
  * online upgrade      — swap the model/optimizer module version mid-run
                          with state migration (examples/online_upgrade_demo).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core.interface import BentoModule
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed.sharding import ShardingCtx
from repro.models import lm, params as P
from repro.optim.adamw import adamw_init_specs
from repro.train.step import make_train_step
from repro import checkpoint as ckpt


def _init_state(pspecs, ospecs, run: RunConfig, rng):
    """Fresh parameters and optimizer state from their specs."""
    return (P.materialize(pspecs, rng, dtype=run.param_dtype),
            P.materialize(ospecs, rng, dtype="float32"))


def _state_shapes(pspecs, ospecs, run: RunConfig):
    """Shapes and dtypes of the state ``_init_state`` makes, without
    materializing it."""
    return jax.eval_shape(functools.partial(_init_state, pspecs, ospecs, run),
                          jax.random.PRNGKey(0))


def state_nbytes(cfg: ModelConfig, run: RunConfig) -> int:
    """Bytes of a Trainer's parameters plus optimizer state — what one
    checkpoint stores — from their specs, without materializing them."""
    pspecs = lm.param_specs(cfg)
    shapes = _state_shapes(pspecs, adamw_init_specs(pspecs, run), run)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


class WorkerFailure(Exception):
    """Simulated node loss (tests inject it via failure_hook)."""


class Trainer(BentoModule):
    NAME = "trainer"
    VERSION = 1

    def __init__(self, cfg: ModelConfig, run: RunConfig, *, global_batch: int,
                 seq_len: int, mesh=None, ruleset: str = "baseline",
                 seed: int = 0, ckpt_view=None, ckpt_root: str = "/ckpt",
                 ckpt_every: int = 0, ckpt_pipeline_depth: Optional[int] = None,
                 failure_hook: Optional[Callable[[int], None]] = None,
                 data=None):
        self.cfg, self.run = cfg, run
        self.global_batch, self.seq_len = global_batch, seq_len
        self.seed = seed
        self.ckpt_view, self.ckpt_root, self.ckpt_every = ckpt_view, ckpt_root, ckpt_every
        # None defers to the checkpoint store's default/env knob; 0 pins
        # the serial reference engine (restores stay byte-identical)
        self.ckpt_pipeline_depth = ckpt_pipeline_depth
        self.failure_hook = failure_hook
        self.metrics_log: list = []
        self.recoveries = 0
        self.data = data or SyntheticLM(cfg, global_batch, seq_len, seed=seed)
        self._build(mesh, ruleset)
        # made from the seed when first read (see ``params``), so a job
        # that restarts from a checkpoint never materializes random state
        self._params = self._opt_state = None
        self.step_idx = 0
        self.last_restore_stats: Dict[str, Any] = {}
        self._prefetch: Optional[Prefetcher] = None

    # --- build / init -----------------------------------------------------------
    def _build(self, mesh, ruleset: str) -> None:
        self.mesh = mesh
        self.ruleset = ruleset
        self.ctx = (ShardingCtx.for_mesh(mesh, ruleset) if mesh is not None
                    else ShardingCtx.null())
        self.pspecs = lm.param_specs(self.cfg)
        self.ospecs = adamw_init_specs(self.pspecs, self.run)
        # what a restore fills in: a restarted job knows only these
        self._shapes = _state_shapes(self.pspecs, self.ospecs, self.run)
        fn = make_train_step(self.cfg, self.run, self.ctx, self.global_batch)
        if mesh is not None:
            from repro.launch.programs import _ns_tree
            self.param_shardings = _ns_tree(self.pspecs, self.ctx)
            self.opt_shardings = _ns_tree(self.ospecs, self.ctx)
            self._step_fn = jax.jit(
                fn, out_shardings=(self.param_shardings, self.opt_shardings, None),
                donate_argnums=(0, 1))
        else:
            self.param_shardings = self.opt_shardings = None
            self._step_fn = jax.jit(fn, donate_argnums=(0, 1))

    def _init_state(self) -> None:
        # one compiled program for the whole state, made where it is
        # sharded: op by op, an accelerator compiles each op of each leaf
        shardings = (None if self.param_shardings is None
                     else (self.param_shardings, self.opt_shardings))
        init = jax.jit(functools.partial(_init_state, self.pspecs,
                                         self.ospecs, self.run),
                       out_shardings=shardings)
        self._params, self._opt_state = init(jax.random.PRNGKey(self.seed))

    @property
    def params(self):
        if self._params is None:
            self._init_state()
        return self._params

    @params.setter
    def params(self, value) -> None:
        self._params = value

    @property
    def opt_state(self):
        if self._opt_state is None:
            self._init_state()
        return self._opt_state

    @opt_state.setter
    def opt_state(self, value) -> None:
        self._opt_state = value

    def drop_state(self) -> None:
        """Free the state in HBM, as a killed job loses it: only what a
        checkpoint holds comes back (``restore_checkpoint``); reading the
        state before that makes a fresh one from the seed."""
        for x in jax.tree.leaves((self._params, self._opt_state)):
            if isinstance(x, jax.Array):
                x.delete()
        self._params = self._opt_state = None

    # --- stepping ------------------------------------------------------------------
    def _fetch(self, step: int) -> Dict[str, np.ndarray]:
        return self.data.batch(step)

    def train(self, n_steps: int) -> Dict[str, float]:
        """Supervised loop with recovery; returns final metrics."""
        last = {}
        self._prefetch = Prefetcher(self._fetch, start_step=self.step_idx)
        try:
            while self.step_idx < n_steps:
                try:
                    if self.failure_hook is not None:
                        self.failure_hook(self.step_idx)
                    sidx, batch = self._prefetch.next()
                    assert sidx == self.step_idx, (sidx, self.step_idx)
                    last = self.run_step(batch)
                    if (self.ckpt_every and self.ckpt_view is not None
                            and self.step_idx % self.ckpt_every == 0):
                        self.save_checkpoint()
                except WorkerFailure:
                    self.recoveries += 1
                    self.recover()
        finally:
            if self._prefetch:
                self._prefetch.close()
                self._prefetch = None
        return last

    def run_step(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        self.params, self.opt_state, metrics = self._step_fn(
            self.params, self.opt_state, batch)
        m = {k: float(v) for k, v in metrics.items()}
        m["step"] = self.step_idx
        self.metrics_log.append(m)
        self.step_idx += 1
        return m

    # --- §4.8 state transfer ------------------------------------------------------------
    def extract_state(self) -> Dict[str, Any]:
        return {
            "params": jax.device_get(self.params),
            "opt_state": jax.device_get(self.opt_state),
            "step": self.step_idx,
            "seed": self.seed,
        }

    def restore_state(self, state: Dict[str, Any], from_version: int = 1) -> None:
        params, opt = state["params"], state["opt_state"]
        if self.param_shardings is not None:
            params = jax.device_put(params, self.param_shardings)
            opt = jax.device_put(opt, self.opt_shardings)
        self.params, self.opt_state = params, opt
        self.step_idx = state["step"]
        new_seed = state.get("seed", self.seed)
        if new_seed != self.seed and isinstance(self.data, SyntheticLM):
            self.data = SyntheticLM(self.cfg, self.global_batch, self.seq_len,
                                    seed=new_seed)
        self.seed = new_seed

    def state_schema(self):
        return ("params", "opt_state", "step", "seed")

    # --- checkpoint / recovery -------------------------------------------------------------
    def _ckpt_shardings(self):
        if self.param_shardings is None:
            return None
        return {"params": self.param_shardings, "opt": self.opt_shardings}

    def _ckpt_services(self):
        """The mount's kernel services (None on a FUSE mount): their hash
        checksums every shard on save and verifies it on restore."""
        return getattr(getattr(self.ckpt_view, "m", None), "services", None)

    def save_checkpoint(self) -> None:
        """Shard-per-file v2 save: the live shardings become the stored
        shard grid, so a restart on a different mesh reshards on restore
        instead of gathering full tensors."""
        assert self.ckpt_view is not None
        ks = self._ckpt_services()
        root = f"{self.ckpt_root}/step_{self.step_idx:08d}"
        extra = None
        if self.mesh is not None:
            from repro.launch.mesh import mesh_axis_sizes
            extra = {"mesh_axes": mesh_axis_sizes(self.mesh),
                     "ruleset": self.ruleset}
        ckpt.save(self.ckpt_view, root,
                  {"params": self.params, "opt": self.opt_state},
                  step=self.step_idx, shardings=self._ckpt_shardings(),
                  checksum=ks.checksum if ks is not None else None,
                  extra=extra, pipeline_depth=self.ckpt_pipeline_depth)

    def restore_checkpoint(self, step: Optional[int] = None) -> bool:
        assert self.ckpt_view is not None
        if step is None:
            step = ckpt.latest_step(self.ckpt_view, self.ckpt_root)
        if step is None:
            return False
        root = f"{self.ckpt_root}/step_{step:08d}"
        # the state in HBM, if any, is neither read nor needed
        params, opt = self._shapes
        like = {"params": params, "opt": opt}
        self.last_restore_stats = {}
        ks = self._ckpt_services()
        tree, _mf = ckpt.load(
            self.ckpt_view, root, like,
            checksum=ks.checksum if ks is not None else None,
            checksum_batch=ks.checksum_batch if ks is not None else None,
            sharding_tree=self._ckpt_shardings(),
            stats=self.last_restore_stats,
            pipeline_depth=self.ckpt_pipeline_depth)
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step_idx = step
        # job-restart latency is the fleet-scale payoff: report how much
        # of the restore's fetch work the pipeline hid behind assembly
        pipe = self.last_restore_stats.get("pipeline", {})
        self.last_restore_stats["overlap_ratio"] = \
            pipe.get("overlap_ratio", 0.0)
        return True

    def recover(self) -> None:
        """Node-failure path: restore last durable state and replay."""
        if self._prefetch:
            self._prefetch.close()
        if self.ckpt_view is not None and self.restore_checkpoint():
            pass  # restored from FS
        else:
            self._init_state()  # cold restart
            self.step_idx = 0
        self._prefetch = Prefetcher(self._fetch, start_step=self.step_idx)

    # --- elastic rescale ----------------------------------------------------------------------
    def elastic_rescale(self, new_mesh, ruleset: str = "baseline") -> None:
        """Quiesce -> extract -> rebuild for the new mesh -> restore."""
        state = self.extract_state()
        if self._prefetch:
            self._prefetch.close()
            self._prefetch = None
        self._build(new_mesh, ruleset)
        self.restore_state(state)
