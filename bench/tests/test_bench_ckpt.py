"""The checkpoint cycle at a CPU size: it agrees with its reference, and
the comparison fails under the control and under each fault the cell
can have."""

import jax

import benchtiny
from benchkit.generators import ckpt_cycle

CELL = "ckpt-smollm135m.cycle"


def test_agrees_with_reference():
    result, lines = benchtiny.run(CELL)
    assert result["correct"], lines
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"save_stall_s", "resume_s", "setup_s"}


def test_traced_run_reports_the_counters():
    result, lines = benchtiny.run(CELL, trace=True)
    assert result["correct"], lines
    m = result["metrics"]
    # the CPU has no device plane, so the device's share is left out
    assert "device.idle_share.ckpt" not in m
    assert m["journal.write_amp.save"]["value"] > 2.0
    assert 0 < m["hash_roofline.save"]["value"] < 100
    assert m["ckpt.restore_fetch_s"]["value"] > 0


def test_control_fails():
    result, _ = benchtiny.run(CELL, control=True)
    assert not result["correct"]
    assert result["compared"]["restored_leaves_wrong"]["value"] > 0


def _after_setup(monkeypatch, fault):
    orig = ckpt_cycle.Generator.setup

    def setup(self):
        orig(self)
        fault()

    monkeypatch.setattr(ckpt_cycle.Generator, "setup", setup)


def test_fault_save_leaves_the_device_unchanged(monkeypatch):
    from repro.train.trainer import Trainer
    _after_setup(monkeypatch, lambda: monkeypatch.setattr(
        Trainer, "save_checkpoint", lambda self: None))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]


def test_fault_half_of_the_shards_left_out(monkeypatch):
    from repro.fs.posix import PosixView
    write_many = PosixView.write_many

    def half(self, items, **kw):
        items = list(items)
        return write_many(self, items[:max(1, len(items) // 2)], **kw)

    _after_setup(monkeypatch,
                 lambda: monkeypatch.setattr(PosixView, "write_many", half))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]


def test_fault_restored_state_altered(monkeypatch):
    from repro.train.trainer import Trainer
    restore = Trainer.restore_checkpoint

    def altered(self, *a, **kw):
        ok = restore(self, *a, **kw)
        leaves, tree = jax.tree.flatten(self.params)
        self.params = jax.tree.unflatten(tree, [leaves[0] + 1] + leaves[1:])
        return ok

    _after_setup(monkeypatch, lambda: monkeypatch.setattr(
        Trainer, "restore_checkpoint", altered))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]
    assert result["compared"]["restored_leaves_wrong"]["value"] > 0
