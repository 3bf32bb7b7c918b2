"""The entry points' persistent compilation cache lands in one directory:
``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed git-ignored path in
the checkout.  Each case runs in a subprocess so the process-wide JAX
config of the test worker stays untouched."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
if len(sys.argv) > 1:  # compile one program and let the cache keep it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
print(json.dumps({"path": str(path), "default": str(DEFAULT_DIR),
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir: Path | None, compile_one: bool = False) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _PROBE] + (["compile"] if compile_one else [])
    out = subprocess.run(argv, capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_directory_is_used_and_no_other(tmp_path):
    cache = tmp_path / "jax-cache"
    res = _probe(cache, compile_one=True)
    assert res["path"] == str(cache)
    assert res["config"] == str(cache)
    assert any(cache.iterdir()), "no cache entry written to the env dir"


def test_default_is_one_fixed_ignored_path_in_the_checkout():
    first, second = _probe(None), _probe(None)
    assert first == second  # fixed: no pid-, time- or tmp-based path
    path = Path(first["path"])
    assert first["config"] == str(path) == first["default"]
    assert path.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert f"{path.name}/" in ignored
