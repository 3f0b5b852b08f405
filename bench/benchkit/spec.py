"""Find a cell, its configuration, its traffic mix, its metrics and the
device's peaks by name. Nothing here knows a cell by name: every entry
is a file under ``bench/`` named in ``BENCHMARK.json``."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if "workloads" not in m
            or cell in m["workloads"]]


def resolve(bm: Dict, cell: str, root: Path = ROOT) -> Dict:
    """Everything one cell runs with: the workload entry, the config and
    traffic files read, and the metric specs that apply to it."""
    try:
        wl = next(w for w in bm["workloads"] if w["name"] == cell)
    except StopIteration:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json") from None
    conf = next(c for c in bm["configs"] if c["name"] == wl["config"])
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{wl['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = _for_cell(bm["end_to_end"], cell)
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in _for_cell(bm["per_layer"], cell)
                 if m["moves"] in moved]
    return {"workload": wl, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def metric_reader(name: str, root: Path = ROOT) -> Callable[[Dict],
                                                            Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read(record)``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, root: Path = ROOT) -> Dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]
