"""Where the entry points put JAX's persistent compilation cache
(:mod:`repro.jaxcache`): ``$JAX_COMPILATION_CACHE_DIR`` when set and
nowhere else, else the fixed ``<checkout>/.jax_cache``.  Each case runs
in a CPU-only child, because JAX fixes the cache directory at its first
compile."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.jaxcache import CHECKOUT, compile_cache_dir

CHILD = """
import sys
from pathlib import Path
from repro.jaxcache import use_compile_cache
print(use_compile_cache(Path(sys.argv[1])))
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
"""


def test_default_cache_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert (CHECKOUT / "src" / "repro" / "jaxcache.py").is_file()
    assert compile_cache_dir() == str(CHECKOUT / ".jax_cache")
    assert compile_cache_dir() == compile_cache_dir()


@pytest.mark.parametrize("from_env", [True, False])
def test_compiled_entries_land_only_in_the_chosen_dir(tmp_path, from_env):
    root = tmp_path / "checkout"
    root.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   [str(CHECKOUT / "src"), env.get("PYTHONPATH", "")]))
    want = root / ".jax_cache"
    if from_env:
        want = tmp_path / "from_env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", CHILD, str(root)],
                         env=env, cwd=tmp_path, text=True,
                         capture_output=True, timeout=120, check=True)
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    made = {p for p in tmp_path.rglob("*") if p.is_file()}
    assert made and all(want in p.parents for p in made)
