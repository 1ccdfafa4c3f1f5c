"""Placement of JAX's persistent compilation cache (launch.compile_cache).

Each case runs in a fresh interpreter: JAX fixes the cache directory at
a process's first compile, so the suite's own process cannot show it.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_DIR, cache_entries

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
path = configure_compile_cache()
if COMPILE:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
print(json.dumps({"path": path,
                  "dir": jax.config.jax_compilation_cache_dir,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = _PROBE.replace("COMPILE", repr(compile_))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_places_the_cache(tmp_path):
    target = tmp_path / "cache"
    got = _probe(target, compile_=True)
    assert got["path"] == got["dir"] == str(target)
    assert got["min_s"] == 0.0
    assert cache_entries(str(target)) > 0


def test_default_is_a_fixed_ignored_directory_in_the_checkout():
    got = _probe(None, compile_=False)
    assert got["path"] == got["dir"] == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
