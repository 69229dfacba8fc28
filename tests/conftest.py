"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; only launch/dryrun.py forces 512."""
import importlib.util
import os
import sys

# make `import repro` work without installing
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# property tests prefer real hypothesis; fall back to the bundled sampler
# stub (tests/_hypothesis_stub.py) in containers that don't ship it
try:
    import hypothesis  # noqa: F401
except ImportError:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis", os.path.join(os.path.dirname(__file__),
                                   "_hypothesis_stub.py"))
    _stub = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_stub)
    sys.modules["hypothesis"] = _stub

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.config.base import ModelConfig  # noqa: E402

# ---------------------------------------------------------------- models
#: the canonical tiny dense model the serving tests drive (one shared
#: definition instead of a copy per test module)
TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97)

#: one config per layer-kind family engine surgery (graft, chunked
#: prefill, fuzz) must round-trip — parametrize over sorted(KIND_CFGS);
#: the 5 layer families plus the all-windowed small-window edge case
KIND_CFGS = {
    "global": TINY,
    "windowed": dataclasses.replace(TINY, name="tiny-win",
                                    block_pattern=("attn", "local_attn"),
                                    sliding_window=16),
    "rglru": dataclasses.replace(TINY, name="tiny-rg", family="hybrid",
                                 block_pattern=("rglru", "attn")),
    "rwkv": dataclasses.replace(TINY, name="tiny-rwkv", family="ssm",
                                d_model=64, block_pattern=("rwkv",),
                                rwkv_head_size=32),
    "tail": dataclasses.replace(TINY, name="tiny-tail", n_layers=3,
                                block_pattern=("attn", "attn")),
    # every layer a ring buffer, window SMALLER than typical chunk
    # sizes — the wraparound edge chunked prefill must round-trip
    "swa": dataclasses.replace(TINY, name="tiny-swa", sliding_window=8,
                               block_pattern=("local_attn",)),
}


def flat_live_blocks(tables, pos, active, bs: int, width: int):
    """A flat decode input built by a plain loop, the reference for the
    engine's: each active row's blocks through ``pos`` as (block id,
    row, first position), padded with the null block (row -1) to
    ``width``."""
    cols = [(int(tables[b, c]), b, c * bs)
            for b in active for c in range(int(pos[b]) // bs + 1)]
    cols += [(0, -1, 0)] * (width - len(cols))
    return np.asarray(cols, np.int32).T


def tiny_variant(**overrides) -> ModelConfig:
    """A one-off TINY derivative (name it, or collide in jit caches)."""
    return dataclasses.replace(TINY, **overrides)


def make_cont_engine(cfg: ModelConfig = TINY, max_slots: int = 2,
                     max_seq: int = 64, **kw):
    """Continuous-engine factory with the suite's default tiny shape."""
    from repro.serving.engine import ContinuousBatchingEngine

    return ContinuousBatchingEngine(cfg, max_slots=max_slots,
                                    max_seq=max_seq, **kw)


def make_pool(cfg: ModelConfig = TINY, max_instances: int = 2,
              max_slots: int = 2, max_seq: int = 64, **kw):
    """Single-model pool factory over the shared tiny config."""
    from repro.serving.runtime import ModelInstancePool

    return ModelInstancePool({cfg.name: cfg}, max_instances=max_instances,
                             max_slots=max_slots, max_seq=max_seq, **kw)


@pytest.fixture(scope="session")
def tiny_cfg() -> ModelConfig:
    return TINY


@pytest.fixture(params=sorted(KIND_CFGS))
def kind_cfg(request) -> ModelConfig:
    """Parametrized fixture over the layer-family configs."""
    return KIND_CFGS[request.param]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)
