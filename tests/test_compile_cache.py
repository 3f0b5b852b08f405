"""``enable_compile_cache`` puts JAX's persistent cache in exactly one
place: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``.jax_cache``
at the repository root. Each case runs a fresh process on a copy of the
module, so the copy's root stands in for the repository."""

import os
import shutil
import subprocess
import sys

import pytest

from repro.launch import compile_cache

CODE = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("cc", sys.argv[1])
cc = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cc)
import jax, jax.numpy as jnp
print(cc.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(4)).block_until_ready()
"""


def test_repo_cache_is_at_the_repo_root():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(compile_cache.REPO_CACHE) == os.path.join(root, ".jax_cache")


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_one_place(tmp_path, env_set):
    repo = tmp_path / "repo"
    mod = repo / "src" / "repro" / "launch" / "compile_cache.py"
    mod.parent.mkdir(parents=True)
    shutil.copy(compile_cache.__file__, mod)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env_dir = tmp_path / "env_cache"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", CODE, str(mod)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want, other = ((env_dir, repo / ".jax_cache") if env_set
                   else (repo / ".jax_cache", env_dir))
    assert proc.stdout.strip() == str(want)
    assert any(want.iterdir()), "no cache entry was written"
    assert not other.exists()
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        ["repo", want.name] if env_set else ["repo"])
