"""``repro.launch.compile_cache``: where programs keep compiled code.

Each case runs in a child process, so the parent's JAX configuration and
its in-memory cache state stay untouched.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR

REPO = Path(__file__).resolve().parent.parent

_PROGRAM = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(tmp_path, env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _PROGRAM.format(compile=compile_)],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_env_dir_is_used_and_nothing_is_set(tmp_path):
    cache = tmp_path / "cache"
    used, configured = _run(tmp_path, cache, True)
    assert used == configured == str(cache)
    assert any(cache.iterdir())


def test_default_dir_is_fixed_and_ignored(tmp_path):
    used, configured = _run(tmp_path, None, False)
    assert used == configured == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
