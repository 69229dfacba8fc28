"""Shared layers: initialisers, norms, MLPs, embeddings.

All layers are pure functions ``apply(params, x, cfg)`` over nested-dict
params; initialisers mirror them with ``init(rng, ...)`` returning the dict.
Compute runs in the dtype of ``x``; norm statistics accumulate in f32.
Every product against a stored parameter goes through :func:`dense`, so
weights stored narrower than the activations are never widened.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- init
def dense_init(rng, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype):
    return (jax.random.normal(rng, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------- products
def dense(x: jax.Array, w: jax.Array, spec: str | None = None) -> jax.Array:
    """``x @ w``, or ``jnp.einsum(spec, x, w)``, for activations ``x``
    and a stored parameter ``w``. Where ``w`` is a narrower float than
    ``x`` (bfloat16 weights under a float32 residual stream), ``x`` is
    rounded to ``w``'s type and the product accumulates and returns in
    ``x``'s type: the arithmetic of a default-precision float32 product
    on the TPU, with no float32 copy of ``w``. Otherwise the plain
    product."""
    kw = {}
    if jnp.issubdtype(w.dtype, jnp.floating) \
            and jnp.dtype(w.dtype).itemsize < jnp.dtype(x.dtype).itemsize:
        kw["preferred_element_type"] = x.dtype
        x = x.astype(w.dtype)
    if spec is None:
        return jnp.matmul(x, w, **kw)
    return jnp.einsum(spec, x, w, **kw)


# ---------------------------------------------------------------- norms
def norm_init(d: int, kind: str, dtype) -> Dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
        out = xf / rms * p["scale"].astype(jnp.float32)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) / jnp.sqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        raise ValueError(f"unknown norm {kind!r}")
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MLP
def mlp_init(rng, d: int, f: int, gated: bool, dtype) -> Dict:
    ks = jax.random.split(rng, 3)
    p = {"w_up": dense_init(ks[0], d, f, dtype),
         "w_down": dense_init(ks[1], f, d, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d, f, dtype)
    return p


def apply_mlp(p: Dict, x: jax.Array, activation: str) -> jax.Array:
    # "silu" -> swiGLU (gated), "geglu" -> gated gelu, "gelu" -> plain MLP
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    up = dense(x, p["w_up"])
    if "w_gate" in p:
        up = act(dense(x, p["w_gate"])) * up
    else:
        up = act(up)
    return dense(up, p["w_down"])


# ---------------------------------------------------------------- embed
def apply_embed(table: jax.Array, tokens: jax.Array,
                dtype=None) -> jax.Array:
    """The table's rows for ``tokens``, cast to ``dtype`` if given (the
    residual stream's type where the table is stored narrower)."""
    rows = jnp.take(table, tokens, axis=0)
    return rows if dtype is None else rows.astype(dtype)


def unembed(x: jax.Array, table_or_head: jax.Array, tied: bool,
            softcap: float | None = None) -> jax.Array:
    """x: (..., d) -> logits (..., V). ``table_or_head`` is (V, d) if tied
    (the embedding table) else (d, V)."""
    if tied:
        logits = dense(x, table_or_head.T)
    else:
        logits = dense(x, table_or_head)
    if softcap:
        logits = softcap * jnp.tanh(logits.astype(jnp.float32) / softcap)
        logits = logits.astype(x.dtype)
    return logits
