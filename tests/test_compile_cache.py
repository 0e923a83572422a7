"""``repro.compile_cache``: where compiled programs are kept.

Each case runs in a child process, so this test process's own JAX
configuration is never touched.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import CACHE_DIRNAME, checkout_cache_dir

ROOT = Path(__file__).resolve().parent.parent

_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from repro.compile_cache import configure_compile_cache
path = configure_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps({"returned": path,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def _child(env_dir, mode: str) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD, mode], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_dir_is_used_and_no_other(tmp_path):
    cache = tmp_path / "cache"
    got = _child(cache, "compile")
    assert got == {"returned": str(cache), "jax": str(cache)}
    # every compile is kept, however short
    assert any(p.name.startswith("jit__lambda") for p in cache.iterdir())


def test_default_dir_is_fixed_in_the_checkout():
    want = ROOT / CACHE_DIRNAME
    assert checkout_cache_dir() == want
    got = _child(None, "configure-only")
    assert got == {"returned": str(want), "jax": str(want)}
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{CACHE_DIRNAME}/" in ignored
