#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chips it names.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, its traffic file and its per-layer
metric readers are found by name from ``BENCHMARK.json``. The run sets
up (compile included), measures for ``--seconds`` seconds (whole saves
and resumes, or whole Filebench flows), frees the program's state,
compares what the window produced with the plain reference, and prints
one JSON line last on stdout: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and
``compared``, each number of the comparison beside its limit. The same
numbers are the last lines on stderr.

It fails, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for. JAX's persistent compilation cache is turned on by
``repro.launch.compile_cache``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None, *, control: bool = False) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchkit import spec

    cell = spec.resolve(spec.load_benchmark(), args.workload)
    # libtpu would otherwise log under a fixed /tmp path, outside the run's own
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    chips = int(cell["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, so set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from benchkit.cell import run_cell

    result, lines = run_cell(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), devices=devices[:chips],
                             peaks=peaks, t_start=T0, control=control)
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
