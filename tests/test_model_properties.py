"""Property tests on model-layer invariants (hypothesis)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import KIND_CFGS

from repro.models.rope import apply_rope, default_positions


# ---------------------------------------------------------------- RoPE
@given(seed=st.integers(0, 100), s=st.integers(2, 16))
@settings(max_examples=25, deadline=None)
def test_rope_preserves_norm(seed, s):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, s, 2, 64)), jnp.float32)
    pos = default_positions(1, s)
    y = apply_rope(x, pos, "rope")
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-4)


def test_rope_relative_property():
    """q·k after RoPE depends only on relative distance."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 1, 64)), jnp.float32)

    def score(pq, pk):
        qr = apply_rope(q, jnp.full((1, 1), pq, jnp.int32), "rope")
        kr = apply_rope(k, jnp.full((1, 1), pk, jnp.int32), "rope")
        return float(jnp.sum(qr * kr))

    assert score(5, 3) == pytest.approx(score(105, 103), rel=1e-3)
    assert score(5, 3) != pytest.approx(score(5, 4), rel=1e-3)


def test_rope2d_rotates_only_half():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 4, 1, 64)), jnp.float32)
    pos = default_positions(1, 4)
    y = apply_rope(x, pos, "rope2d")
    # second half of head dim passes through
    np.testing.assert_array_equal(np.asarray(x[..., 32:]),
                                  np.asarray(y[..., 32:]))
    assert not np.allclose(np.asarray(x[..., 1:, :, :32]),
                           np.asarray(y[..., 1:, :, :32]))


def test_mrope_equals_rope_for_text():
    """With t=h=w=linear positions, M-RoPE must reduce to plain RoPE on
    the score level for equal positions."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 6, 1, 64)), jnp.float32)
    pos = default_positions(1, 6)
    y1 = apply_rope(x, pos, "mrope", mrope_positions=(pos, pos, pos))
    y2 = apply_rope(x, pos, "mrope")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


# ---------------------------------------------------------------- MoE
@given(seed=st.integers(0, 50), top_k=st.integers(1, 2))
@settings(max_examples=12, deadline=None)
def test_moe_conserves_token_mass(seed, top_k):
    """Every kept token's output is a convex combination over experts;
    dropped tokens produce zeros (residual path)."""
    from repro.config.base import ModelConfig
    from repro.models.moe import moe_apply, moe_init

    cfg = ModelConfig(name="m", family="moe", d_model=32, d_ff=64,
                      n_experts=4, top_k=top_k, capacity_factor=8.0,
                      vocab_size=64)
    rng = jax.random.PRNGKey(seed)
    p = moe_init(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 8, 32))
    y, aux = moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert float(aux["drop_frac"]) == 0.0  # cf=8 => no drops
    assert float(aux["lb_loss"]) >= 0.99  # >= 1 by Cauchy-Schwarz


def test_moe_capacity_drops_are_bounded():
    from repro.config.base import ModelConfig
    from repro.models.moe import moe_apply, moe_init

    cfg = ModelConfig(name="m", family="moe", d_model=32, d_ff=64,
                      n_experts=4, top_k=1, capacity_factor=0.5,
                      vocab_size=64)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y, aux = moe_apply(p, x, cfg)
    assert 0.0 < float(aux["drop_frac"]) < 1.0


# ---------------------------------------------------------------- windows
@given(window=st.sampled_from([4, 8, 16]))
@settings(max_examples=6, deadline=None)
def test_sliding_window_blocks_distant_context(window):
    """Changing a token OUTSIDE the window must not change attention
    output; inside the window it must."""
    from repro.config.base import ModelConfig
    from repro.models.attention import attn_init, attention_full
    from repro.models.rope import default_positions

    cfg = ModelConfig(name="w", family="dense", d_model=64, n_heads=2,
                      n_kv_heads=2, d_ff=128, vocab_size=64,
                      sliding_window=window)
    p = attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    S = 32
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, 64))
    pos = default_positions(1, S)
    base = attention_full(p, x, cfg, pos, window=window, impl="naive")
    # perturb the FIRST token: outputs at positions >= window must not move
    x2 = x.at[:, 0, :].add(10.0)
    pert = attention_full(p, x2, cfg, pos, window=window, impl="naive")
    np.testing.assert_allclose(np.asarray(base[:, window:, :]),
                               np.asarray(pert[:, window:, :]), atol=1e-5)
    assert not np.allclose(np.asarray(base[:, :window, :]),
                           np.asarray(pert[:, :window, :]), atol=1e-3)


@pytest.mark.parametrize("kind", sorted(KIND_CFGS))
def test_init_params_vmapped_units_match_per_layer_init(kind):
    """Each scan-stacked unit kind is initialised by one vmap over its
    layer keys: bit-identical to initialising every layer on its own key
    and stacking the results."""
    from repro.models.transformer import _layer_init, _stack, init_params

    base = KIND_CFGS[kind]
    cfg = dataclasses.replace(base, n_layers=3 * base.n_layers)
    rng = jax.random.PRNGKey(3)
    params = init_params(rng, cfg)
    ks = iter(jax.random.split(rng, 64))
    next(ks)                       # embed
    if not cfg.tie_embeddings:
        next(ks)                   # lm_head
    n_units = cfg.n_layers // len(cfg.block_pattern)
    assert n_units > 1
    for pos, layer_kind in enumerate(cfg.block_pattern):
        want = _stack([_layer_init(next(ks), cfg, layer_kind, jnp.float32,
                                   cfg.enc_dec) for _ in range(n_units)])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params["units"][pos], want)
