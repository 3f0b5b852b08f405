"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run holds, and a
runner that skips the harness's look for a chip. The widths, the
fileset and the thread count shrink; the generators, the comparison and the
metric readers are the ones the chip runs."""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchkit import spec  # noqa: E402

CPU_PEAKS = {"hbm_bytes_per_s": 819e9}  # only so the readers have a peak
SEED = 2**31 + 4321  # more than 32 signed bits hold


def tiny(cell_name: str):
    cell = spec.resolve(spec.load_benchmark(), cell_name)
    conf, traffic = cell["config"], cell["traffic"]
    if traffic["generator"] == "ckpt_cycle":
        conf.update(hidden_size=48, intermediate_size=128,
                    num_attention_heads=3, num_key_value_heads=1,
                    vocab_size=256, num_hidden_layers=2)
        conf["job"] = {"global_batch": 2, "seq_len": 32}
        traffic["steps_between_saves"] = 2
    else:
        conf.update(nfiles=64, device_blocks=16384)
        traffic["nthreads"] = 4
    return cell


def run(cell_name: str, *, seconds: float = 1.0, trace: bool = False,
        seed: int = SEED, control: bool = False):
    import jax
    from benchkit.cell import run_cell

    return run_cell(tiny(cell_name), seed=seed, seconds=seconds, trace=trace,
                    devices=jax.devices()[:1], peaks=CPU_PEAKS,
                    t_start=time.perf_counter(), control=control)
