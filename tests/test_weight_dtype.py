"""Served weights stored narrower than the activations.

The continuous engine stores its parameter tree in ``weight_dtype``
(bfloat16 by default on the TPU) while its KV pool, staging caches,
residual stream and logits stay in ``dtype`` (float32). Every product
against a parameter goes through ``layers.dense``, which rounds the
activation to the weight's type and accumulates in the activation's, so
no step widens a weight again. Covered on the CPU for the paged
attention family and the hybrid recurrent (RG-LRU) family: the types
of every tree, the logits against a float32-stored engine handed the
same values, the backend default through the pool, and the served
bytes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import KIND_CFGS, make_cont_engine, make_pool
from repro.models.layers import dense
from repro.serving.engine import ContinuousBatchingEngine

#: logits of a bfloat16-stored engine lie within this share of the
#: largest float32 logit of the same weights: a few bfloat16 roundings
#: (2^-8 each) of the products' activations, not a wrong weight
BF16_LOGIT_TOL = 2 ** -5

FAMILIES = ["global", "rglru"]


def _engine(cfg, weight_dtype):
    return make_cont_engine(cfg, block_size=8,
                            weight_dtype=weight_dtype)


def _record_logits(eng):
    """Wrap the engine's jitted steps: every call's (step, logits)."""
    calls = []
    for name in ("_prefill_chunk", "_decode"):
        step = getattr(eng, name)

        def wrapped(params, cache, batch, _step=step, _name=name):
            logits, cache = _step(params, cache, batch)
            calls.append((_name, np.asarray(logits)))
            return logits, cache
        setattr(eng, name, wrapped)
    return calls


def _dtypes(tree):
    return {str(a.dtype) for a in jax.tree.leaves(tree)}


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 97, size=n).astype(np.int32) for n in (5, 11, 19)]


@pytest.mark.parametrize("family", FAMILIES)
def test_bf16_weights_keep_f32_pool_stream_and_logits(family):
    eng = _engine(KIND_CFGS[family], jnp.bfloat16)
    assert eng.weight_dtype == jnp.bfloat16 and eng.dtype == jnp.float32
    assert _dtypes(eng.params) == {"bfloat16"}
    assert _dtypes(eng.cache) == {"float32"}
    x = eng.model.embed(eng.params, jnp.ones((2, 3), jnp.int32))
    assert x.dtype == jnp.float32
    calls = _record_logits(eng)
    eng.run(_prompts(), max_new_tokens=4)
    assert {name for name, _ in calls} == {"_prefill_chunk", "_decode"}
    assert all(logits.dtype == np.float32 for _, logits in calls)
    assert _dtypes(eng.cache) == {"float32"}


@pytest.mark.parametrize("family", FAMILIES)
def test_bf16_logits_match_f32_storage_of_the_same_values(family):
    """The same weight values stored in float32 (exact float32 products
    on the CPU) give the same tokens, and every prefill and decode
    call's logits within a bfloat16 rounding."""
    cfg = KIND_CFGS[family]
    e16 = _engine(cfg, jnp.bfloat16)
    e32 = _engine(cfg, jnp.float32)
    e32.params = jax.tree.map(lambda a: a.astype(jnp.float32), e16.params)
    c16, c32 = _record_logits(e16), _record_logits(e32)
    r16 = e16.run(_prompts(), max_new_tokens=6)
    r32 = e32.run(_prompts(), max_new_tokens=6)
    assert [list(r.tokens) for r in r16] == [list(r.tokens) for r in r32]
    assert [n for n, _ in c16] == [n for n, _ in c32]
    for (name, a), (_, b) in zip(c16, c32):
        gap = np.abs(a - b).max()
        assert gap <= BF16_LOGIT_TOL * np.abs(b).max(), (name, gap)


@pytest.mark.parametrize("family", FAMILIES)
def test_param_bytes_halve_in_bf16(family):
    cfg = KIND_CFGS[family]
    b16 = _engine(cfg, jnp.bfloat16).stats()["param_bytes"]
    b32 = _engine(cfg, jnp.float32).stats()["param_bytes"]
    assert b16 > 0 and 2 * b16 == b32


def _pool_engine():
    pool = make_pool(block_size=8)
    pool.scale_to("tiny", 1)
    return pool.live("tiny")[0].engine


def test_pool_engine_stores_f32_weights_on_the_cpu():
    eng = _pool_engine()
    assert eng.weight_dtype == jnp.float32
    assert _dtypes(eng.params) == {"float32"}


def test_pool_engine_stores_bf16_weights_on_the_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _pool_engine()
    assert eng.weight_dtype == jnp.bfloat16
    assert _dtypes(eng.params) == {"bfloat16"}
    assert _dtypes(eng.cache) == {"float32"}


def test_share_from_refuses_a_donor_of_another_weight_type():
    cfg = KIND_CFGS["global"]
    donor = _engine(cfg, jnp.bfloat16)
    with pytest.raises(ValueError, match="types"):
        ContinuousBatchingEngine(cfg, max_slots=2, max_seq=64,
                                 share_from=donor, weight_dtype=jnp.float32)
    twin = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=64,
                                    share_from=donor,
                                    weight_dtype=jnp.bfloat16)
    assert twin.params is donor.params


def test_dense_rounds_the_activation_to_a_narrower_weight():
    """f32 activations against bf16 weights: the activation rounded to
    bf16, exact products, f32 result, and no f32 copy of the weight."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8)).astype(jnp.bfloat16)
    out = dense(x, w)
    assert out.dtype == jnp.float32
    want = jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                      w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    jaxpr = jax.make_jaxpr(dense)(x, w).jaxpr
    widened = [e for e in jaxpr.eqns
               if e.primitive.name == "convert_element_type"
               and e.outvars[0].aval.shape == w.shape]
    assert not widened


def test_dense_is_the_plain_product_otherwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.float32)
    np.testing.assert_array_equal(dense(x, w), x @ w)
    buf = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 16), jnp.float32)
    e = jax.random.normal(jax.random.PRNGKey(3), (4, 16, 8), jnp.float32)
    np.testing.assert_array_equal(dense(buf, e, "ecd,edf->ecf"),
                                  jnp.einsum("ecd,edf->ecf", buf, e))
