"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: the same phases
and checks as on the chip, with the Pallas kernels in interpret mode and
the host crc32 bound where no TPU is. And the script itself refuses to
run anywhere but on a TPU."""

import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from repro.configs import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    bundle = registry.get(chip_smoke.ARCH)
    return bundle.smoke, bundle.run.replace(microbatch_per_data_shard=0)


def test_main_refuses_a_host_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == "" and "'platform': 'cpu'" in err


def test_blockhash_phase_interpreted(capsys):
    chip_smoke.phase_blockhash(np.random.default_rng(0),
                               long_bytes=300 * 4096 + 3, interpret=True)
    assert '"phase": "blockhash"' in capsys.readouterr().out


def test_checkpoint_cycle_phase(smoke, capsys):
    cfg, run = smoke
    chip_smoke.phase_checkpoint_cycle(cfg, run, batch=2, seq=128, steps=2,
                                      seed=0, impl="crc32")
    out = capsys.readouterr().out
    assert '"phase": "restore"' in out and '"bytes_identical": true' in out


def test_elastic_phase_on_four_host_devices():
    """The ``--chips 4`` phase on four virtual CPU devices, with the
    attention kernel interpreted under its ``shard_map``."""
    code = ("import chip_smoke\n"
            "from repro.configs import registry\n"
            "b = registry.get(chip_smoke.ARCH)\n"
            "run = b.run.replace(microbatch_per_data_shard=0)\n"
            "chip_smoke.phase_elastic(b.smoke, run, batch=8, seq=128, "
            "steps=2, seed=0, impl='crc32')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_ATTN="pallas_interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    restores = [line for line in proc.stdout.splitlines()
                if '"phase": "elastic_restore"' in line]
    assert len(restores) == 3
