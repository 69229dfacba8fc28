"""Sharding-spec divisibility coverage across the layer families
(docs/ARCHITECTURE.md §11).

Two rule sets are checked, both against the invariant jax enforces at
``device_put``/``jit`` time — every axis a spec shards must DIVIDE its
dim on the target mesh (jax rejects uneven sharding outright):

* the launch-scale rules (``param_pspec``/``cache_shardings``) on the
  2x2 debug mesh, over one reduced config per layer family —
  attention, windowed, RWKV, RG-LRU, and a frontend (vision) stack;
* the serving-engine rules (``engine_param_shardings`` /
  ``engine_cache_shardings``) on a 2-way TP mesh, over the tiny
  serving configs — here the invariant must hold for ARBITRARY dims
  (odd vocab, 2-head caches) because ``_fit_mesh`` drops any
  non-dividing axis to replicated.

Runs in a SUBPROCESS with 4 forced host devices (mesh construction
needs them; the main test process keeps its single device).
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: one reduced config per layer family (attention / windowed / rwkv /
#: rglru / frontend) — starcoder2 is the sliding-window family,
#: qwen2-vl carries the vision frontend stack
FAMILY_ARCHS = ("qwen3-0.6b", "starcoder2-15b", "rwkv6-3b",
                "recurrentgemma-2b", "qwen2-vl-7b")

_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.config import get_reduced_config
from repro.config.base import ModelConfig
from repro.launch.mesh import make_debug_mesh, make_tp_mesh
from repro.launch import sharding
from repro.models import build_model
from repro.common.tree import tree_map_with_path

def assert_divides(shardings, arrays, mesh, ctx):
    leaves_s = dict()
    tree_map_with_path(lambda p, s: leaves_s.__setitem__(p, s), shardings)
    n_sharded = 0
    def chk(path, leaf):
        nonlocal n_sharded
        spec = leaves_s[path].spec
        assert len(spec) <= leaf.ndim, (ctx, path, tuple(spec), leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (ctx, path, spec, leaf.shape)
            n_sharded += 1
    tree_map_with_path(chk, arrays)
    return n_sharded

mesh = make_debug_mesh(2, 2)
for arch in {archs!r}:
    cfg = get_reduced_config(arch)
    model = build_model(cfg, remat=False)
    params = model.abstract_params(jnp.float32)
    n = assert_divides(sharding.param_shardings(mesh, params),
                       params, mesh, arch)
    assert n > 0, f"{{arch}}: no parameter leaf sharded at all"
    cache = model.cache_spec(4, 128, jnp.float32)
    assert_divides(sharding.cache_shardings(mesh, cfg, cache, 4),
                   cache, mesh, arch)
    print(arch, "OK", n)

# engine rules: arbitrary (odd) dims must still satisfy the invariant
tp = make_tp_mesh(2)
for kwargs in (dict(name="tiny", family="dense",
                    block_pattern=("attn",)),
               dict(name="tiny-w", family="dense",
                    block_pattern=("local_attn",), sliding_window=16),
               dict(name="tiny-rwkv", family="ssm",
                    block_pattern=("rwkv",), rwkv_head_size=16),
               dict(name="tiny-rglru", family="ssm",
                    block_pattern=("rglru",)),
               dict(name="tiny-bias", family="dense",
                    block_pattern=("attn",), qkv_bias=True)):
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=97, **kwargs)
    model = build_model(cfg, remat=False)
    params = model.abstract_params(jnp.float32)
    n = assert_divides(sharding.engine_param_shardings(tp, params),
                       params, tp, cfg.name)
    assert n > 0, f"{{cfg.name}}: no parameter leaf sharded at all"
    cache = model.cache_spec(3, 128, jnp.float32)
    assert_divides(sharding.engine_cache_shardings(tp, cache),
                   cache, tp, cfg.name)
    print("engine", cfg.name, "OK", n)
print("DONE")
"""


@pytest.mark.slow
def test_sharding_specs_divide_mesh_all_families():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    code = _CODE.format(archs=FAMILY_ARCHS)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    for arch in FAMILY_ARCHS:
        assert f"{arch} OK" in out.stdout
    assert "DONE" in out.stdout


def test_qkv_bias_shards_like_the_projection_columns():
    """bq/bk/bv split on ``model`` as the output dim of wq/wk/wv does, so
    each head shard adds its own heads' bias; stacked under a unit, the
    leading layer dim stays whole."""
    from repro.launch.sharding import _base_spec, param_pspec

    class Leaf:
        def __init__(self, shape):
            self.shape, self.ndim = shape, len(shape)

    for name in ("bq", "bk", "bv"):
        assert _base_spec(f"units/0/attn/{name}", 1) == ("model",)
        assert tuple(param_pspec(f"units/0/attn/{name}", Leaf((10, 4096)))) \
            == (None, "model")
        assert tuple(param_pspec(f"tail/0/attn/{name}", Leaf((256,)))) \
            == ("model",)
