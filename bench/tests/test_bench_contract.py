"""Every cell of BENCHMARK.json resolves to files of its own, the peaks
table refuses an unknown device, and the command refuses to run without
a TPU."""

import json
import os
import subprocess
import sys

import pytest

import benchtiny  # noqa: F401 — puts bench/ and src/ on the path
from benchkit import spec

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(BM, cell)
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    assert os.path.exists(os.path.join(
        spec.BENCH, "benchkit", "generators",
        c["traffic"]["generator"] + ".py"))


def test_every_config_key_that_differs_is_reduced():
    for conf in BM["configs"]:
        with open(spec.ROOT / conf["file"]) as f:
            data = json.load(f)
        assert set(data["reduced"]) == set(conf["reduced"])


def test_peaks_known_and_unknown_kind():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert not p.stdout.strip()
