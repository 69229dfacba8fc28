"""Tensor-parallel sharded engine tests (docs/RUNTIME.md §10).

The sharded engine spans a 1D ``("model",)`` mesh: params land under
``engine_param_shardings`` (column-sharded wq/wk/wv, row-sharded wo),
the KV block pool head-shards over the model axis, and
decode/prefill/verify are jitted with NamedSharding in/out specs. The
acceptance bar is TOKEN IDENTITY: a sharded engine must produce
bit-identical greedy outputs to the unsharded engine on the same
weights, across block sizes, prefix cache on/off, speculation, and TP
degrees 2 and 4.

Multi-device tests run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must
be set before jax imports; the main test process keeps its single
device). Error-path tests that need no devices run in-process.
"""
import os
import subprocess
import sys

import pytest

from conftest import TINY
from repro.serving.engine import ContinuousBatchingEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.config.base import ModelConfig
from repro.launch.mesh import make_tp_mesh
from repro.serving.engine import ContinuousBatchingEngine

TINY2 = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                    n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97)
TINY4 = ModelConfig(name="tiny4", family="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=97)

def prompts(cfg, seed=7):
    # shared-prefix family (prefix-cache hit + full-cover duplicate)
    # plus a divergent one-off
    rng = np.random.default_rng(seed)
    v = cfg.vocab_size
    shared = rng.integers(1, v, 20).astype(np.int32)
    ps = [np.concatenate([shared, rng.integers(1, v, n).astype(np.int32)])
          for n in (4, 12)]
    ps += [rng.integers(1, v, 9).astype(np.int32), ps[0].copy()]
    return ps

def toks(cfg, mesh, share_from=None, **kw):
    eng = ContinuousBatchingEngine(cfg, max_slots=3, max_seq=128, seed=0,
                                   mesh=mesh, share_from=share_from, **kw)
    return eng, [r.tokens for r in eng.run(prompts(cfg),
                                           max_new_tokens=8)]

def template_ids(pool):
    return sorted((m, tuple(d.id for d in ds)) for m, ds in pool._templates)

def check(name, ref, got):
    assert len(ref) == len(got), name
    for i, (r, g) in enumerate(zip(ref, got)):
        assert np.array_equal(r, g), (name, i, r, g)
    print(name, "OK")
"""


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _PRELUDE + code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_engine_token_identity(tp):
    """Sharded greedy outputs == unsharded, at TP degree 2 and 4, for
    block size 8 (prefix cache on AND off, token budget on; decode reads
    the flat live blocks) and the default block size 16 (the tables).
    tp=4 head-shards a 4-head variant; the 2-head config proves the
    divisibility filter (heads replicate, projections still shard) on
    the 2-way mesh."""
    cfg = "TINY2" if tp == 2 else "TINY4"
    out = _run_sub(f"""
cfg = {cfg}
mesh = make_tp_mesh({tp})
_, ref = toks(cfg, None, block_size=8)
donor, got = toks(cfg, mesh, block_size=8)
check("paged", ref, got)
# blocks of 8: every decode step read the flat live blocks (width 32)
assert donor.n_decode_flat_steps == donor.n_iters > 0
print("flat OK")
_, got = toks(cfg, mesh, block_size=8,
              prefix_cache=True, token_budget=24)
check("paged_prefix_budget", ref, got)
_, ref16 = toks(cfg, None)
_, got16 = toks(cfg, mesh)
check("block16", ref16, got16)
_, got = toks(cfg, mesh, share_from=donor, block_size=8)
check("share_from", ref, got)
try:
    ContinuousBatchingEngine(
        ModelConfig(name="bad", family="dense", n_layers=1, d_model=30,
                    n_heads=3, n_kv_heads=3, d_ff=32, vocab_size=97),
        max_slots=1, max_seq=64, seed=0, mesh=mesh)
except ValueError as e:
    assert "must divide" in str(e), e
    print("divide OK")
""")
    for name in ("paged", "flat", "paged_prefix_budget", "block16",
                 "share_from", "divide"):
        assert f"{name} OK" in out


@pytest.mark.slow
def test_sharded_engine_speculative_identity():
    """Speculative decode (propose/verify/rollback) on a 2-way mesh
    stays token-identical to the unsharded plain-decode engine."""
    out = _run_sub("""
mesh = make_tp_mesh(2)
_, ref = toks(TINY2, None, block_size=8)
_, got = toks(TINY2, mesh, block_size=8,
              prefix_cache=True, spec_k=3)
check("speculative", ref, got)
""")
    assert "speculative OK" in out


@pytest.mark.slow
def test_sharded_engine_token_identity_with_qkv_bias():
    """A ChatGLM3-shaped model (QKV bias, eps 1e-5, partial rotary, 2 KV
    heads) head-shards on a 2-way mesh, its biases split like the output
    columns of wq/wk/wv, and stays token-identical to tp=1."""
    out = _run_sub("""
import dataclasses
cfg = dataclasses.replace(TINY4, name="tiny4-glm", n_kv_heads=2,
                          qkv_bias=True, norm_eps=1e-5, rope="rope2d")
mesh = make_tp_mesh(2)
_, ref = toks(cfg, None, block_size=8)
eng, got = toks(cfg, mesh, block_size=8)
attn = eng.params["units"][0]["attn"]
for name in ("bq", "bk", "bv"):
    assert tuple(attn[name].sharding.spec) == (None, "model"), name
assert float(abs(attn["bq"]).max()) > 0.0
check("glm_bias", ref, got)
""")
    assert "glm_bias OK" in out


@pytest.mark.slow
def test_pool_spawns_tp_instances():
    """ModelInstancePool carves TP instances from the FREE devices of
    the shared set: instances span disjoint meshes (tp=1 included),
    devices_in_use sums the degrees, head-sharding discounts the KV
    budget charge (one budget block buys tp pool blocks), each device
    set keys its own weight/jit template, outputs stay identical to a
    plain engine, and set_tp_degree drains mismatched instances so the
    next scale_to respawns at the new degree."""
    out = _run_sub("""
from repro.serving.runtime import ModelInstancePool

pool = ModelInstancePool({"tiny": TINY2}, max_instances=4, max_slots=2,
                         max_seq=128, block_size=8,
                         kv_block_budget=64, tp_degree=2, n_devices=8)
assert pool.scale_to("tiny", 2) == 2
insts = pool.running("tiny")
assert all(i.tp_degree == 2 for i in insts)
assert all(i.engine.tp_degree == 2 for i in insts)
assert pool.devices_in_use() == 4
# full grant 2*16=32 blocks; at tp=2 the budget charge halves
# while the engine keeps the full grant
for i in insts:
    assert i.kv_blocks == 16 and i.engine.allocator.n_blocks == 32
assert pool.kv_blocks_free == 64 - 32
# disjoint device pairs, one weight/jit template per device set
assert [[d.id for d in i.devices] for i in insts] == [[0, 1], [2, 3]]
assert template_ids(pool) == [("tiny", (0, 1)), ("tiny", (2, 3))]
print("spawn OK")

ref = ContinuousBatchingEngine(TINY2, max_slots=1, max_seq=128, seed=0)
ps = prompts(TINY2)
want = [ref.run([p], max_new_tokens=4)[0].tokens for p in ps]
rids = {pool.submit("tiny", p, slo_ms=60_000.0, max_new_tokens=4): i
        for i, p in enumerate(ps)}
got = {rids[r.request_id]: r.tokens
       for r in pool.run_until_drained()}
for i, w in enumerate(want):
    assert np.array_equal(got[i], w), (i, got[i], w)
print("identity OK")

pool.set_tp_degree("tiny", 1)
assert not pool.running("tiny")      # old degree drains
assert pool.scale_to("tiny", 1) == 1
inst = pool.running("tiny")[0]
# the draining pair still holds devices 0-3: the new one lands on 4
assert inst.tp_degree == 1 and [d.id for d in inst.devices] == [4]
assert inst.kv_blocks == inst.engine.allocator.n_blocks == 32
pool.step()                          # sweep retires the drained pair
assert pool.devices_in_use() == 1
assert template_ids(pool) == [("tiny", (4,))]
assert pool.kv_blocks_free == 64 - 32
print("retune OK")
""")
    for name in ("spawn", "identity", "retune"):
        assert f"{name} OK" in out


@pytest.mark.slow
def test_pool_device_budget_bounds_joint_partition():
    """m_c and TP degree jointly partition the shared device set:
    scale_to clamps when Σ tp_degree would exceed n_devices. Without a
    device budget, tp>1 instances still take disjoint devices."""
    out = _run_sub("""
from repro.serving.runtime import ModelInstancePool

pool = ModelInstancePool({"tiny4": TINY4}, max_instances=8, max_slots=2,
                         max_seq=128, tp_degree=4, n_devices=8)
assert pool.scale_to("tiny4", 3) == 2   # 3 x tp=4 > 8 devices
assert pool.devices_in_use() == 8
assert not pool.can_spawn("tiny4")
pool.set_tp_degree("tiny4", 1)
pool._sweep()                           # retire the drained degree-4 pair
assert pool.devices_in_use() == 0
assert pool.scale_to("tiny4", 3) == 3
assert pool.devices_in_use() == 3
print("budget OK")

# without a device budget, higher degrees still carve disjoint sets
free = ModelInstancePool({"tiny4": TINY4}, max_instances=8, max_slots=2,
                         max_seq=128, tp_degree=2)
assert free.scale_to("tiny4", 2) == 2
assert [[d.id for d in i.devices] for i in free.running("tiny4")] \
    == [[0, 1], [2, 3]]
print("unbudgeted OK")
""")
    assert "budget OK" in out and "unbudgeted OK" in out


# ------------------------------------------------- in-process error paths
def test_mesh_helpers_raise_actionable_errors():
    """Device-count failures must name the mesh being built and the
    XLA_FLAGS workaround (the raw jax error names neither)."""
    from repro.launch.mesh import (make_debug_mesh, make_production_mesh,
                                   make_tp_mesh)
    for build, pat in ((lambda: make_tp_mesh(64), "make_tp_mesh(64)"),
                       (lambda: make_debug_mesh(16, 16),
                        "make_debug_mesh(16, 16)"),
                       (lambda: make_production_mesh(),
                        "make_production_mesh")):
        with pytest.raises(ValueError) as exc:
            build()
        msg = str(exc.value)
        assert pat in msg and "XLA_FLAGS" in msg \
            and "--xla_force_host_platform_device_count" in msg
    with pytest.raises(ValueError):
        make_tp_mesh(0)


def test_engine_validates_mesh():
    """A mesh without a 'model' axis is rejected at construction (the
    head-divisibility rejection needs a >1-device mesh and is covered
    by the subprocess identity test above)."""
    import jax
    import numpy as np_

    mesh = jax.sharding.Mesh(np_.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="'model' axis"):
        ContinuousBatchingEngine(TINY, max_slots=1, max_seq=64, seed=0,
                                 mesh=mesh)


def test_guard_degrades_tp_degree_before_concurrency():
    """An infeasible TP degree steps down BEFORE m_c or b degrade: the
    collective surcharge and the device claim go first (the ladder is
    k → token budget → tp → m_c → b). With a 1-device budget the
    degree-2 half of the action space is never applied, so this runs
    on the single-device test process."""
    from conftest import make_pool
    from repro.config.base import ServingConfig
    from repro.serving.bcedge import PoolScheduler

    pool = make_pool()
    pool.n_devices = 1
    scfg = ServingConfig(batch_sizes=(1, 2), concurrency_levels=(1,),
                         tp_degrees=(1, 2))
    sched = PoolScheduler(pool, scfg, slo_ms={m: 1000.0
                                              for m in pool.configs},
                          decode_steps_mean=1.0, learn=False, seed=0)
    model = next(iter(pool.configs))
    a = scfg.quint_to_action(2, 1, 0, 0, 2)
    applied = sched._apply(model, a)
    assert scfg.action_to_quint(applied) == (2, 1, 0, 0, 1)
    assert sched.guard_interventions == 1
    assert pool.tp_degrees[model] == 1
    # the 12th state feature is the shared-device-set utilization
    from repro.serving.bcedge import POOL_STATE_DIM
    s = sched._state(model)
    assert s.shape == (POOL_STATE_DIM,)
    pool.scale_to(model, 1)
    assert sched._state(model)[11] == 1.0  # 1 of 1 devices in use
