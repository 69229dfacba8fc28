"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned arch runs one forward/train step on CPU — output shapes + no NaNs.
Full configs are exercised only via the dry-run (ShapeDtypeStruct)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import INPUT_SHAPES, get_config, get_reduced_config

# one forward/train step per architecture adds up to minutes: excluded
# from the tier-1 CI job, covered by the full-suite job (pytest.ini)
pytestmark = pytest.mark.slow
from repro.models import build_model
from repro.train.optimizer import adam, apply_updates

ARCHS = [
    "llama4-maverick-400b-a17b", "rwkv6-3b", "starcoder2-15b",
    "qwen2-vl-7b", "recurrentgemma-2b", "chatglm3-6b",
    "seamless-m4t-large-v2", "yi-34b", "arctic-480b", "qwen3-0.6b",
]


def make_batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.enc_dec:
        batch["frontend_embeds"] = jnp.asarray(
            rng.standard_normal((B, max(8, S // 4), cfg.d_model)) * 0.1,
            jnp.float32)
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    elif cfg.frontend is not None:
        F = cfg.frontend_tokens
        batch["frontend_embeds"] = jnp.asarray(
            rng.standard_normal((B, F, cfg.d_model)) * 0.1, jnp.float32)
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S - F)), jnp.int32)
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, batch["tokens"].shape), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_constraints(arch):
    cfg = get_reduced_config(arch)
    assert cfg.n_layers <= 3
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4
    assert get_config(arch).family == cfg.family


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)

    loss = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    # one SGD-ish train step must also be finite and change params
    opt = adam(1e-3)
    opt_state = opt.init(params)
    grads = jax.grad(model.loss)(params, batch)
    for g in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(g).all()), f"{arch}: non-finite grads"
    updates, _ = opt.update(grads, opt_state, params)
    new_params = apply_updates(params, updates)
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_params)))
    assert moved, f"{arch}: train step did not change params"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_shapes(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = make_batch(cfg, B=B, S=S)
    batch.pop("labels")
    logits, cache = model.prefill(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite prefill"

    total = S if not (cfg.frontend and not cfg.enc_dec) else S
    from repro.models.transformer import pad_cache

    cache = pad_cache(cfg, cache, 4)
    db = {"tokens": jnp.ones((B, 1), jnp.int32),
          "pos": jnp.full((B,), total, jnp.int32)}
    logits2, cache2 = model.decode_step(params, cache, db)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits2).all()), f"{arch}: non-finite decode"


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "rwkv6-3b",
                                  "recurrentgemma-2b", "chatglm3-6b",
                                  "qwen2-vl-7b", "arctic-480b"])
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode must reproduce the full-sequence logits."""
    cfg = get_reduced_config(arch)
    model = build_model(cfg, remat=False, attn_impl="naive")
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    rng = np.random.default_rng(3)
    batch = make_batch(cfg, B=B, S=S, seed=3)
    batch.pop("labels")

    # full prefill over S tokens -> last-token logits
    full_logits, _ = model.prefill(params, batch)

    # prefill S-1 tokens, then decode token S-1 step by step
    short = dict(batch)
    short["tokens"] = batch["tokens"][:, :-1]
    _, cache = model.prefill(params, short)
    from repro.models.transformer import pad_cache

    cache = pad_cache(cfg, cache, 2)
    # total context = S for every family (vlm: F frontend + (S-F) text)
    pos = jnp.full((B,), S - 1, jnp.int32)
    step_logits, _ = model.decode_step(
        params, cache, {"tokens": batch["tokens"][:, -1:], "pos": pos})
    np.testing.assert_allclose(
        np.asarray(step_logits, np.float32),
        np.asarray(full_logits, np.float32), atol=2e-3, rtol=2e-3)


def test_full_configs_match_assignment():
    spec = {
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048, 128),
        "rwkv6-3b": (32, 2560, None, None, 8960, 65536, 0),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152, 0),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064, 0),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000, 0),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024, 0),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256208, 0),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000, 0),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000, 128),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936, 0),
    }
    for arch, (L, d, H, KV, ff, V, E) in spec.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        if H is not None:
            assert cfg.n_heads == H, arch
            assert cfg.n_kv_heads == KV, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab_size == V, arch
        assert cfg.n_experts == E, arch
    # ChatGLM3 as published: QKV bias and its own RMSNorm epsilon; every
    # other preset keeps neither
    glm = get_config("chatglm3-6b")
    assert glm.qkv_bias and glm.norm_eps == 1e-5 and glm.rope == "rope2d"
    for arch in spec:
        if arch != "chatglm3-6b":
            assert not get_config(arch).qkv_bias, arch
            assert get_config(arch).norm_eps == 1e-6, arch


def test_param_count_estimates():
    """Analytic param counts should land near the advertised sizes."""
    import math

    targets = {"llama4-maverick-400b-a17b": (400e9, 0.25),
               "yi-34b": (34e9, 0.15),
               "arctic-480b": (480e9, 0.15),
               "qwen3-0.6b": (0.6e9, 0.25),
               "starcoder2-15b": (15e9, 0.25),
               "rwkv6-3b": (3e9, 0.4)}
    for arch, (target, tol) in targets.items():
        got = get_config(arch).param_count_estimate()
        assert math.isclose(got, target, rel_tol=tol), \
            f"{arch}: {got/1e9:.1f}B vs {target/1e9:.0f}B"
