"""Where compiled programs land: ``JAX_COMPILATION_CACHE_DIR`` when it
is set, ``<checkout>/.jax_cache`` otherwise. Each case runs a fresh
interpreter over a copy of the module placed in a scratch checkout, so
neither this process's JAX config nor the real checkout is touched."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

MODULE = (Path(__file__).resolve().parents[1]
          / "src" / "repro" / "common" / "compile_cache.py")

PROBE = """
import importlib.util, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print(mod.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", (True, False))
def test_compiled_entries_land_in_one_place(tmp_path, env_set):
    checkout = tmp_path / "checkout"
    module = checkout / "src" / "repro" / "common" / "compile_cache.py"
    module.parent.mkdir(parents=True)
    shutil.copy(MODULE, module)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env_dir = tmp_path / "env_cache"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", PROBE, str(module)], env=env,
        capture_output=True, text=True, timeout=300, check=True)
    expected = env_dir if env_set else checkout / ".jax_cache"
    other = checkout / ".jax_cache" if env_set else env_dir
    assert proc.stdout.splitlines()[0] == str(expected)
    assert any(p.name.endswith("-cache") for p in expected.iterdir())
    assert not other.exists()
