"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: ``compile_cache.enable()`` changes
process-wide JAX configuration, which must not leak into other tests.
"""

import os
import pathlib
import subprocess
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

PROBE = """
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print(compile_cache.enable())
jax.jit(lambda x: jnp.sort(x) * 3 + 1)(jnp.arange(100)).block_until_ready()
s = compile_cache.stats()
print(s["hits"], s["misses"])
"""


def _run(env_updates):
    env = dict(os.environ, **env_updates)
    env["PYTHONPATH"] = str(SRC)
    env["JAX_PLATFORMS"] = "cpu"
    # cache every program, however quickly it compiles
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, check=True)
    lines = out.stdout.strip().splitlines()
    hits, misses = map(int, lines[-1].split())
    return lines[-2], hits, misses


def test_env_dir_is_used_and_a_second_run_hits(tmp_path):
    cache = tmp_path / "cache"
    env = {"JAX_COMPILATION_CACHE_DIR": str(cache)}
    path, hits, misses = _run(env)
    assert path == str(cache)
    assert misses > 0 and hits == 0
    assert any(cache.iterdir())
    _, hits, misses = _run(env)
    assert hits > 0 and misses == 0


def test_default_dir_is_fixed_inside_the_checkout():
    from repro.launch import compile_cache

    root = SRC.parent
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    # enable() alone configures the directory; nothing compiles here
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", "from repro.launch import compile_cache; "
         "print(compile_cache.enable())"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.strip().splitlines()[-1] == str(root / ".jax_cache")
