"""The persistent compilation cache is placed from outside: the
environment variable wins and nothing else is set, otherwise the cache
sits at one fixed path inside the checkout, the same in every call and
every process."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import (ENV_VAR, compile_cache_dir,
                                        enable_compile_cache)

ROOT = Path(__file__).resolve().parent.parent


def test_environment_variable_wins_and_nothing_is_set(monkeypatch,
                                                      tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_across_calls_and_processes(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = compile_cache_dir()
    assert path == compile_cache_dir()
    assert Path(path) == ROOT / ".jax_cache"
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [path, path]
