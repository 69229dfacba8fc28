"""The entry points' persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says and nowhere else; unset, it goes to a
fixed directory inside the checkout. Importing an entry point turns
nothing on. Each case runs in a subprocess: the setting is process-wide.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CODE = """
import json, os
import jax, jax.numpy as jnp
import repro.launch.serve  # importing an entry point enables nothing
assert (jax.config.jax_compilation_cache_dir or None) \
    == (os.environ.get("JAX_COMPILATION_CACHE_DIR") or None)
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
before = sorted(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else None
used = enable_compile_cache()
if os.environ.get("COMPILE"):
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(7)).block_until_ready()
after = sorted(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else None
print(json.dumps({"used": used, "default": str(DEFAULT_DIR),
                  "config": jax.config.jax_compilation_cache_dir,
                  "default_untouched": before == after}))
"""


def _run(env_updates):
    import json

    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_updates)
    out = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_nothing_else(tmp_path):
    cache = tmp_path / "jax-cache"
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(cache), "COMPILE": "1"})
    assert got["used"] == got["config"] == str(cache)
    assert any(cache.iterdir()), "nothing was cached in the env directory"
    assert got["default_untouched"]


@pytest.mark.parametrize("env_value", [None, ""], ids=["unset", "empty"])
def test_fixed_in_checkout_dir_without_env(env_value):
    got = _run({} if env_value is None
               else {"JAX_COMPILATION_CACHE_DIR": env_value})
    repo = os.path.realpath(os.path.join(SRC, ".."))
    assert got["used"] == got["config"] == got["default"] \
        == os.path.join(repo, ".jax_cache")
