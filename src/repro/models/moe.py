"""Mixture-of-Experts FFN with capacity-bucketed scatter/gather dispatch.

TPU-native formulation: tokens are scattered into a dense (E, Cap, d)
buffer (so the per-expert matmul is a single MXU-friendly einsum with the
expert dim shardable over the ``model`` mesh axis = expert parallelism),
then gathered back with their gate weights. Dropped tokens (over capacity)
fall back to the residual path, as in GShard/Switch.

Supports top-1 (llama4-maverick style) and top-2 + dense residual branch
(arctic style).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import apply_mlp, dense, dense_init, mlp_init


def moe_init(rng, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(rng, 5)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, dtype, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * scale
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * scale
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   * (1.0 / jnp.sqrt(f))).astype(dtype),
    }
    if cfg.moe_dense_residual:
        p["dense_mlp"] = mlp_init(ks[4], d, cfg.dense_ff or cfg.d_ff,
                                  gated=True, dtype=dtype)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # >=8, rounded up to a multiple of 8


def moe_apply(p: Dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,d) -> (y (B,S,d), aux metrics incl. load-balance loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    N = B * S
    xf = x.reshape(N, d)
    logits = dense(xf, p["router"]).astype(jnp.float32)  # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (N,k)
    if k > 1:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    cap = _capacity(N, cfg)
    flat_e = gate_idx.reshape(N * k)  # expert id per (token, choice)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)  # (N*k, E)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1.0, flat_e[:, None], axis=1
    )[:, 0].astype(jnp.int32)  # position within expert
    keep = pos < cap
    pos = jnp.where(keep, pos, 0)

    # scatter tokens into (E, Cap, d)
    xk = jnp.repeat(xf, k, axis=0) if k > 1 else xf  # (N*k, d)
    contrib = jnp.where(keep[:, None], xk, 0.0)
    buf = jnp.zeros((E, cap, d), x.dtype).at[flat_e, pos].add(
        contrib.astype(x.dtype))

    # expert swiGLU — expert-parallel over the `model` axis. At decode
    # scale (small capacity) we additionally pin the expert-FFN hidden dim
    # to the `data` axis: GSPMD then contracts partially + psums tiny
    # (E,cap,d) tensors instead of all-gathering the expert weights
    # (§Perf iteration 2 — the weight-gather temp buffers were 12.8 GiB/dev
    # on arctic decode).
    from repro.models.shard_hooks import constrain

    buf = constrain(buf, "model", None, None)
    two_d = cap <= 64
    h = dense(buf, p["w_gate"], "ecd,edf->ecf")
    u = dense(buf, p["w_up"], "ecd,edf->ecf")
    if two_d:
        h = constrain(h, "model", None, "data")
        u = constrain(u, "model", None, "data")
    h = jax.nn.silu(h) * u
    y_e = dense(h, p["w_down"], "ecf,efd->ecd")  # (E,Cap,d)
    y_e = constrain(y_e, "model", None, None)

    # gather back and weight by gates
    y_tok = y_e[flat_e, pos]  # (N*k, d)
    y_tok = y_tok * (gate_vals.reshape(N * k, 1) * keep[:, None]).astype(x.dtype)
    y = y_tok.reshape(N, k, d).sum(axis=1) if k > 1 else y_tok

    # aux: switch-style load-balance loss + router z-loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(frac_tokens * mean_probs)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

    if cfg.moe_dense_residual:
        y = y + apply_mlp(p["dense_mlp"], xf, cfg.activation)

    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "drop_frac": dropped}
    return y.reshape(B, S, d), aux
