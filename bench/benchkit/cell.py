"""Run one cell once: set-up, the measured window (traced or not), the
program's state freed, the reference comparison, the metrics. The
generator, the configuration, the traffic and the metric readers all come
from the resolved cell, so nothing here names a cell."""

from __future__ import annotations

import importlib
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional, Tuple

from benchkit import spec
from benchkit import trace as tr
from benchkit.meter import HashMeter, span


def _compile_counter():
    """Backend compilations from now on, as JAX reports them."""
    import jax

    count = [0]

    def listener(event: str, _secs: float, **_kw) -> None:
        if "backend_compile" in event:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Dict, *, seed: int, seconds: float, trace: bool,
             devices, peaks: Dict, t_start: float, control: bool = False
             ) -> Tuple[Dict, List[str]]:
    """The result line's object and the comparison's lines for stderr."""
    import jax

    meter = HashMeter()
    gen_mod = importlib.import_module(
        f"benchkit.generators.{cell['traffic']['generator']}")
    gen = gen_mod.Generator(cell["config"], cell["traffic"], seed=seed,
                            meter=meter, control=control)
    gen.setup()
    setup_s = time.perf_counter() - t_start

    compiles = _compile_counter()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1  # the harness's annotations, not JAX's
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    n0 = compiles[0]
    t0 = time.perf_counter()
    with span(tr.WINDOW):
        gen.window(seconds)
    window_s = time.perf_counter() - t0
    in_window = compiles[0] - n0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = tr.find_xplane(log_dir)
        reduced = tr.reduce(path) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)
    memory_peak = _memory_peak(devices)
    gen.release()

    t0 = time.perf_counter()
    try:
        checks = gen.check()
    except Exception:  # noqa: BLE001 — a comparison that cannot finish fails
        print(traceback.format_exc(), file=sys.stderr)
        checks = [("check_raised", 1, 0)]
    check_s = time.perf_counter() - t0
    for err in gen.diagnostics()[:3]:
        print(err, file=sys.stderr)

    metrics: Dict[str, Dict] = {}
    if not trace:
        values = dict(gen.end_to_end(), setup_s=setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        record = dict(gen.record(), trace=reduced, peaks=peaks)
        for m in cell["per_layer"]:
            value = spec.metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    correct = gen.failed == 0 and all(value <= limit
                                      for _, value, limit in checks)
    result = {"correct": correct, "attempted": gen.attempted,
              "failed": gen.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in checks}
    lines = [f"setup_s {setup_s}", f"window_s {window_s}",
             f"compiles_in_window {in_window}", f"check_s {check_s}"]
    lines += [f"compared {name} {value} limit {limit}"
              for name, value, limit in checks]
    return result, lines
