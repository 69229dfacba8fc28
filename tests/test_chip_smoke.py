"""chip_smoke.py cannot rot: its phases run here at tiny widths on the
CPU. Only the TPU check is bypassed (the test calls the phase functions
instead of ``main``); the one-chip phases serve the conftest TINY model
over real sockets, the four-chip phases run on four virtual CPU devices.

Each case runs in a subprocess: the HTTP server's threads and the
virtual device count must not leak into the test worker.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

_PRELUDE = """
import dataclasses
import chip_smoke
from repro.config.base import ModelConfig
TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97)
TINY4 = dataclasses.replace(TINY, name="tiny4", n_heads=4, n_kv_heads=4)
"""

CASES = {
    "one_chip": ("", "chip_smoke.one_chip(TINY)\n",
                 ("requests answered over HTTP: 4/4 finished",
                  "greedy token agreement", "peak HBM",
                  "kernel paged_decode float32",
                  "kernel paged_decode_splitk bfloat16",
                  "kernel paged_prefill bfloat16")),
    "four_chips": ("--xla_force_host_platform_device_count=4",
                   "chip_smoke.pool_placement(TINY)\n"
                   "chip_smoke.sharded_serving(TINY4)\n"
                   "chip_smoke.tp_matches_single_chip(TINY4)\n",
                   ("pool tp=2 instance", "pool tp=1 instance",
                    "tiny4 tp=4 answered 2 requests",
                    "tp=4 vs tp=1 first-token logits")),
}


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(CASES))
def test_chip_smoke_phases(case):
    xla_flags, calls, expect = CASES[case]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=xla_flags)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _PRELUDE + calls],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    for line in expect:
        assert line in out.stdout, out.stdout


def test_chip_smoke_refuses_cpu():
    """Without a TPU the script exits non-zero, says why, and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU present" in out.stderr
    assert '"ok"' not in out.stdout
