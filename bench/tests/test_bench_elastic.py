"""The elastic-restart cell at a CPU size on four forced host devices: it
agrees with the whole-tensor reference, and the comparison fails under
the control, with one device shard on the wrong device, and with a
restore under the saving layout. A traced run reads the cell's new
metrics.

``benchtiny.tiny`` knows only the one-chip cells, so the cell is cut here
to the same widths. ``XLA_FLAGS`` must be set before JAX starts, and a
test worker has started it for earlier files, so the runs go in one
subprocess."""

import json
import os
import subprocess
import sys

import pytest

import benchtiny

CELL = "ckpt-smollm135m.elastic4"
NEW_METRICS = ["ckpt.read_amp.resume", "ckpt.run_kib.resume",
               "ckpt.put_s.resume", "ckpt.shard_files.save"]

SCRIPT = r"""
import json, sys, time
import jax
from benchkit import spec
from benchkit.cell import run_cell
from benchkit.generators import ckpt_elastic
from repro.train.trainer import Trainer
import benchtiny

CELL = sys.argv[1]


def tiny():
    cell = spec.resolve(spec.load_benchmark(), CELL)
    cell["config"].update(hidden_size=48, intermediate_size=128,
                          num_attention_heads=3, num_key_value_heads=1,
                          vocab_size=256, num_hidden_layers=2)
    cell["config"]["job"] = {"global_batch": 8, "seq_len": 32}
    cell["traffic"]["steps_between_saves"] = 2
    return cell


def run(trace=False, control=False):
    result, lines = run_cell(tiny(), seed=benchtiny.SEED, seconds=1.0,
                             trace=trace, devices=jax.devices()[:4],
                             peaks=benchtiny.CPU_PEAKS,
                             t_start=time.perf_counter(), control=control)
    return {"correct": result["correct"], "metrics": result["metrics"],
            "compared": {k: v["value"]
                         for k, v in result["compared"].items()},
            "lines": lines}


UNDO = []


def after_setup(fault):
    setup = ckpt_elastic.Generator.setup

    def patched(self):
        setup(self)
        fault(self)

    ckpt_elastic.Generator.setup = patched
    UNDO.append(lambda: setattr(ckpt_elastic.Generator, "setup", setup))


def shard_on_the_wrong_device(gen):
    restore = Trainer.restore_checkpoint

    def swapped(self, *a, **kw):
        ok = restore(self, *a, **kw)
        leaves, tree = jax.tree.flatten(self.params)
        x = next(x for x in leaves
                 if len({str(s.index) for s in x.addressable_shards}) > 1)
        shards = x.addressable_shards
        s0 = shards[0]
        s1 = next(s for s in shards if s.index != s0.index)
        swap = {id(s0): s1, id(s1): s0}
        arrays = [jax.device_put(swap.get(id(s), s).data, s.device)
                  for s in shards]
        y = jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                     arrays)
        self.params = jax.tree.unflatten(
            tree, [y if v is x else v for v in leaves])
        return ok

    Trainer.restore_checkpoint = swapped
    UNDO.append(lambda: setattr(Trainer, "restore_checkpoint", restore))


def restore_under_the_saving_layout(gen):
    a, b = gen.trainers
    a._ckpt_shardings, b._ckpt_shardings = b._ckpt_shardings, \
        a._ckpt_shardings


out = {"plain": run(), "control": run(control=True)}
for name, fault in (("wrong_device", shard_on_the_wrong_device),
                    ("saving_layout", restore_under_the_saving_layout)):
    after_setup(fault)
    out[name] = run()
    while UNDO:
        UNDO.pop()()
out["traced"] = run(trace=True)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [benchtiny.HERE, benchtiny.BENCH,
                    os.path.join(os.path.dirname(benchtiny.BENCH), "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, CELL], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_agrees_with_reference(runs):
    r = runs["plain"]
    assert r["correct"], r["lines"]
    assert r["compared"]["resharded_wrong"] == 0
    assert set(r["metrics"]) == {"save_stall_s", "resume_s", "setup_s"}


@pytest.mark.parametrize("case", ["control", "wrong_device",
                                  "saving_layout"])
def test_comparison_fails(runs, case):
    r = runs[case]
    assert not r["correct"]
    if case != "control":  # the control loses the window's save instead
        assert r["compared"]["resharded_wrong"] > 0


def test_traced_run_reads_the_new_metrics(runs):
    r = runs["traced"]
    assert r["correct"], r["lines"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(m.get(name) is not None for name in NEW_METRICS), m
    assert m["ckpt.shard_files.save"] == 106
    assert m["ckpt.read_amp.resume"] >= 1.0
    assert m["ckpt.put_s.resume"] > 0 and m["ckpt.run_kib.resume"] > 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_reads_nothing(name, monkeypatch):
    """The parent program records ``ckpt.save`` and ``ckpt.restore`` but
    none of what these read: each reader then returns None."""
    from benchkit import program, spec

    span = {"count": 1, "total_s": 1.0, "self_s": 1.0}
    monkeypatch.setattr(program, "_table", lambda: {
        "spans": {"ckpt.save": span, "ckpt.restore": span},
        "counters": {}})
    assert spec.metric_reader(name)({}) is None
