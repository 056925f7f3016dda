"""Where the persistent compilation cache goes, checked in a child process
(JAX reads the cache settings once per process).  The child is pinned to
the CPU: it must never reach for an accelerator the parent may hold."""
import json
import os
import pathlib
import subprocess
import sys

from repro.launch.compile_cache import DEFAULT_DIR

ROOT = pathlib.Path(__file__).resolve().parent.parent

_CHILD = r"""
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
path = configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(5.0)).block_until_ready()
print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir}))
"""


def _run_child(tmp_path, cache_dir):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               TMPDIR=str(tmp_path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    res = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def _entries(d):
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def test_cache_goes_where_the_variable_says(tmp_path):
    cache = tmp_path / "cache"
    before = _entries(DEFAULT_DIR)
    out = _run_child(tmp_path, cache)
    assert out == {"path": str(cache), "config": str(cache)}
    assert _entries(cache)
    assert _entries(DEFAULT_DIR) == before


def test_cache_defaults_to_a_fixed_directory_in_the_checkout(tmp_path):
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    runs = []
    for name in ("a", "b"):  # a different temp dir and pid each time
        (tmp_path / name).mkdir()
        runs.append(_run_child(tmp_path / name, None))
    want = {"path": str(DEFAULT_DIR), "config": str(DEFAULT_DIR)}
    assert runs == [want, want]
    assert _entries(DEFAULT_DIR)
