"""The per-layer metrics read from the program's own spans and counters:
a traced run at the tests' size reports each of them, an untraced run
records nothing, and a program without spans reads nothing and does not
raise."""

import json
import math
import os
import subprocess
import sys

import pytest

import benchtiny
from benchkit import program, spec

BM = spec.load_benchmark()
# the metrics read from the program's spans and counters, by cell
PROGRAM = {
    "ckpt-smollm135m.cycle": [
        "ckpt.d2h_s.save", "ckpt.shard_hash_s.save", "journal.commit_s.save",
        "journal.commit_hash_share.save", "hash.batch_launch_us.save",
        "mount.remount_s.resume", "cache.fill_share.resume"],
    "varmail.t16": [
        "gate.wait_ms.fs", "lock.wait_share.fs", "dir.entries_per_lookup.fs",
        "hash.batch_launch_us.fs"],
}
UNITS = {m["name"]: m["unit"] for m in BM["per_layer"]}


@pytest.mark.parametrize("cell", ["ckpt-smollm135m.cycle", "varmail.t16"])
def test_traced_run_reports_every_program_metric(cell, monkeypatch):
    # the blockhash kernel in interpret mode, so its spans are recorded
    monkeypatch.setenv("REPRO_FORCE_PALLAS_CHECKSUM", "1")
    result, lines = benchtiny.run(cell, trace=True)
    assert result["correct"], lines
    for name in PROGRAM[cell]:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value), name
        if UNITS[name] == "%":
            assert 0 <= value <= 100, name
        elif UNITS[name] == "entries/lookup":
            assert value >= 1
        else:
            assert value > 0, name


def test_untraced_run_records_nothing():
    code = ("import json, benchtiny; from repro.core import spans; "
            "benchtiny.run('varmail.t16', seconds=0.3); "
            "print(json.dumps(spans.snapshot()))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_FORCE_PALLAS_CHECKSUM="1")
    out = subprocess.run([sys.executable, "-c", code], cwd=benchtiny.HERE,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == {"spans": {},
                                                       "counters": {}}


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert program.span_total("ckpt.save") is None
    assert program.counter("dir.lookups") is None
    record = {"counters": {}, "samples": {}, "hash": {}}
    for names in PROGRAM.values():
        for name in names:
            assert spec.metric_reader(name)(record) is None


def test_a_missing_numerator_reads_zero_and_a_missing_denominator_none():
    assert program.per(None, 2.0) == 0.0
    assert program.per(1.0, None) is None
    assert program.per(1.0, 0) is None
    assert program.per(1.0, 4.0, 100.0) == 25.0
