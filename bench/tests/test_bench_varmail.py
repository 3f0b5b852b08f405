"""The Filebench generator at a CPU size: its draw is fixed by the seed, it
agrees with its reference, and the comparison fails under the control
and under each fault the cell can have."""

import numpy as np
import pytest

import benchtiny
from benchkit.generators import filebench
from benchkit.meter import HashMeter

CELL = "varmail.t16"


def _generator(seed, cell=None):
    cell = cell or benchtiny.tiny(CELL)
    return filebench.Generator(cell["config"], cell["traffic"], seed=seed,
                            meter=HashMeter())


def test_draw_is_fixed_by_the_seed():
    a, b = _generator(benchtiny.SEED), _generator(benchtiny.SEED)
    items_a, items_b = a.draw(), b.draw()
    assert items_a == items_b and len(items_a) > 0
    assert [th.rng.integers(0, 1 << 30, 8).tolist() for th in a.threads] \
        == [th.rng.integers(0, 1 << 30, 8).tolist() for th in b.threads]
    assert _generator(benchtiny.SEED + 1).draw() != items_a


def test_fileset_paths_follow_dirwidth():
    cell = benchtiny.tiny(CELL)
    cell["config"].update(nfiles=399, meandirwidth=20)
    d = _generator(1, cell)
    d.draw()
    assert d.fs.levels == 1
    assert d.fs.path(398) == "/bigfileset/d19/f0000398"
    assert len(d.fs.dirs) == 21


def test_agrees_with_reference():
    result, lines = benchtiny.run(CELL)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"ops_per_s", "op_p99_ms", "setup_s"}
    assert list(result)[-1] == "compared"
    assert lines[-1].startswith("compared ")


def test_rates_and_tails_cover_the_whole_window():
    d = _generator(benchtiny.SEED)
    d.setup()
    d.window(0.5)
    lat = d.latencies()
    e2e = d.end_to_end()
    assert lat.size == d.counters["ops"] == d.attempted
    assert e2e["ops_per_s"] == pytest.approx(lat.size / d.window_s)
    assert e2e["op_p99_ms"] == pytest.approx(np.percentile(lat, 99) * 1e3)
    # the window closes when the last thread ends its flow, not before
    assert d.window_s >= 0.5
    d.release()


def test_control_fails():
    result, _ = benchtiny.run(CELL, control=True)
    assert not result["correct"]
    c = result["compared"]
    assert c["files_wrong_after_remount"]["value"] \
        + c["names_wrong_after_remount"]["value"] > 0


def _after_setup(monkeypatch, fault):
    orig = filebench.Generator.setup

    def setup(self):
        orig(self)
        fault()

    monkeypatch.setattr(filebench.Generator, "setup", setup)


def test_fault_append_leaves_the_file_unchanged(monkeypatch):
    from repro.fs.posix import PosixView
    _after_setup(monkeypatch, lambda: monkeypatch.setattr(
        PosixView, "write_many", lambda self, items, **kw: []))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]
    assert result["compared"]["reads_wrong"]["value"] > 0


def test_fault_half_of_each_write_left_out(monkeypatch):
    from repro.fs.posix import PosixView
    write_many = PosixView.write_many

    def half(self, items, **kw):
        items = [(p, off, data[:len(data) // 2]) for p, off, data in items]
        return write_many(self, items, **kw)

    _after_setup(monkeypatch,
                 lambda: monkeypatch.setattr(PosixView, "write_many", half))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]
    assert result["compared"]["reads_wrong"]["value"] > 0


def test_fault_read_answer_altered(monkeypatch):
    from repro.fs.posix import PosixView
    read_many = PosixView.read_many

    def altered(self, specs, **kw):
        return [bytes([r[0] ^ 1]) + r[1:] if isinstance(r, bytes) and r
                else r for r in read_many(self, specs, **kw)]

    _after_setup(monkeypatch,
                 lambda: monkeypatch.setattr(PosixView, "read_many", altered))
    result, _ = benchtiny.run(CELL)
    assert not result["correct"]
    assert result["compared"]["reads_wrong"]["value"] > 0
