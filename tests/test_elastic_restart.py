"""Elastic restart on four devices: a job saves under a 4-way ZeRO split
(``data=4, model=1``) through a ``bento`` mount, is killed, and resumes
under 2-way data x 2-way tensor parallelism (``data=2, model=2``), and
the reverse. The resuming trainer restores through the store's normal
``load`` without materializing random state first; every device shard
is held against the whole-tensor restore of the benchmark's plain
reference (``bench/benchkit/restore_ref.py``).

The four CPU devices are forced through ``XLA_FLAGS``, which must be set
before JAX starts, so the scenario runs in a subprocess: a test worker
has already started JAX for earlier files."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json, tempfile
import jax, numpy as np
from benchkit import restore_ref
from repro.configs import registry
from repro.core import spans
from repro.fs.mounts import blocks_for, make_mount, remount
from repro.launch.mesh import make_elastic_mesh
from repro.train.trainer import Trainer, state_nbytes

b = registry.get("smollm-135m")
cfg, run = b.smoke, b.run.replace(microbatch_per_data_shard=0)
LAYOUTS = {"A": (4, 1), "B": (2, 2)}
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0


def profile():
    return jax.profiler.trace(tempfile.mkdtemp(), profiler_options=opts)


def state(tr):
    return {"params": tr.params, "opt": tr.opt_state}


def targets(tr):
    return jax.tree.leaves({"params": tr.param_shardings,
                            "opt": tr.opt_shardings})


def scenario(src, dst):
    mf = make_mount("bento", n_blocks=blocks_for(2 * state_nbytes(cfg, run)))
    job = Trainer(cfg, run, global_batch=8, seq_len=32, seed=5,
                  mesh=make_elastic_mesh(*LAYOUTS[src]), ckpt_view=mf.view)
    job.train(3)
    saved = jax.tree.leaves(jax.device_get(state(job)))
    with profile():
        job.save_checkpoint()
    shard_files = spans.snapshot()["counters"]["ckpt.save.shard_files"]
    batch = job.data.batch(job.step_idx)
    uninterrupted = job.run_step(batch)["loss"]
    killed = jax.tree.leaves(state(job))
    job.drop_state()  # the kill: no unmount, the state in HBM freed
    freed = all(x.is_deleted() for x in killed)

    inits = []
    init = Trainer._init_state
    Trainer._init_state = lambda self: (inits.append(1), init(self))[1]
    cold = remount(mf.dev)
    tr = Trainer(cfg, run, global_batch=8, seq_len=32, seed=6,
                 mesh=make_elastic_mesh(*LAYOUTS[dst]), ckpt_view=cold.view)
    with profile():
        restored = tr.restore_checkpoint()
        jax.block_until_ready(state(tr))
    counters = spans.snapshot()["counters"]
    inits_before_step = len(inits)

    root = f"/ckpt/step_{tr.step_idx:08d}"
    manifest = json.loads(cold.view.read_file(f"{root}/manifest.json"))
    leaves = jax.tree.leaves(state(tr))
    shards_differ = wrong_sharding = reference_differs = 0
    target_bytes = 0
    for leaf, target, rec, want in zip(leaves, targets(tr),
                                       manifest["leaves"], saved):
        full = restore_ref.whole_leaf(cold.view, rec)
        reference_differs += not np.array_equal(full, np.asarray(want))
        wrong_sharding += not leaf.sharding.is_equivalent_to(target,
                                                             leaf.ndim)
        imap = target.addressable_devices_indices_map(leaf.shape)
        for s in leaf.addressable_shards:
            shards_differ += s.index != imap[s.device] or not \
                np.array_equal(np.asarray(s.data), full[s.index])
            target_bytes += s.data.nbytes
    step = tr.step_idx
    loss = tr.run_step(batch)["loss"]
    return {
        "mesh_axes": manifest["extra"]["mesh_axes"],
        "manifest_shards": sum(len(r["shards"]) for r in manifest["leaves"]),
        "shard_files": shard_files, "freed": freed, "restored": restored,
        "step": step, "inits_before_step": inits_before_step,
        "inits_after_step": len(inits), "reference_differs": reference_differs,
        "shards_differ": shards_differ, "wrong_sharding": wrong_sharding,
        "split_leaves": sum(len({str(s.index) for s in x.addressable_shards})
                            > 1 for x in leaves),
        "target_bytes": target_bytes,
        "bytes_placed": counters.get("ckpt.restore.bytes_placed"),
        "bytes_read": counters.get("ckpt.restore.bytes_read"),
        "uninterrupted_loss": uninterrupted, "resumed_loss": loss,
    }


print(json.dumps({f"{s}->{d}": scenario(s, d)
                  for s, d in (("A", "B"), ("B", "A"))}))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           os.path.join(REPO, "bench")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


DIRECTIONS = ["A->B", "B->A"]
MESH_AXES = {"A": {"data": 4, "model": 1}, "B": {"data": 2, "model": 2}}


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_saved_under_the_source_layout_and_killed(results, direction):
    r = results[direction]
    assert r["mesh_axes"] == MESH_AXES[direction[0]]
    assert r["shard_files"] == r["manifest_shards"] > 34
    assert r["freed"]


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_every_device_shard_is_the_reference_slice(results, direction):
    r = results[direction]
    assert r["restored"] and r["step"] == 3
    assert r["reference_differs"] == 0  # the reference is the saved state
    assert r["shards_differ"] == 0
    assert r["split_leaves"] > 0


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_leaves_carry_the_target_layout(results, direction):
    assert results[direction]["wrong_sharding"] == 0


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_no_random_state_before_the_restore(results, direction):
    r = results[direction]
    assert r["inits_before_step"] == 0
    assert r["inits_after_step"] == 0  # it trains on the restored state


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_restore_counters(results, direction):
    r = results[direction]
    assert r["bytes_placed"] == r["target_bytes"]
    assert r["bytes_read"] >= r["target_bytes"]


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_next_loss_continues_the_job(results, direction):
    """The resumed job's next step starts from the saved state bit for
    bit, but under the other split XLA sums the sharded contractions, and
    rounds their bf16 partial results, in another order. So the loss
    agrees to that rounding and not bit for bit: 3e-5 to 4e-5 apart
    here, held to 5e-4, an eighth of one bf16 step (2^-8)."""
    r = results[direction]
    assert r["resumed_loss"] == pytest.approx(r["uninterrupted_loss"],
                                              rel=5e-4)
