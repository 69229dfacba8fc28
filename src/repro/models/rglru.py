"""RecurrentGemma / Griffin recurrent block: conv1d + RG-LRU.

RG-LRU (real-gated linear recurrent unit)::

    r_t = sigmoid(W_a x_t + b_a)          # recurrence gate
    i_t = sigmoid(W_i x_t + b_i)          # input gate
    log a_t = -c * softplus(Λ) * r_t      # data-gated diagonal decay
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ u_t)

wrapped in the Griffin gated block: a GeLU branch multiplies the recurrent
branch, preceded by a short causal conv1d (width 4). The jnp reference scans
over time; ``repro.kernels.rglru_scan`` is the chunked TPU kernel.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import dense, dense_init, norm_init

CONV_W = 4
DECAY_C = 8.0


def rglru_init(rng, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    w = cfg.rglru_width or d
    ks = iter(jax.random.split(rng, 8))
    # Λ initialised so decay a ∈ (0.9, 0.999) at r=1 (long memory)
    lam = jnp.log(jnp.expm1(-jnp.log(
        jnp.linspace(0.9, 0.999, w, dtype=jnp.float32)) / DECAY_C))
    return {
        "norm": norm_init(d, cfg.norm, dtype),
        "W_x": dense_init(next(ks), d, w, dtype),
        "W_gate": dense_init(next(ks), d, w, dtype),
        "conv_w": (jax.random.normal(next(ks), (CONV_W, w), jnp.float32)
                   * (1.0 / CONV_W)).astype(dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "W_a": dense_init(next(ks), w, w, dtype, scale=0.01),
        "b_a": jnp.zeros((w,), dtype),
        "W_i": dense_init(next(ks), w, w, dtype, scale=0.01),
        "b_i": jnp.zeros((w,), dtype),
        "lam": lam.astype(dtype),
        "W_o": dense_init(next(ks), w, d, dtype),
    }


def _conv1d_causal(u: jax.Array, conv_w: jax.Array, conv_b: jax.Array,
                   hist: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. u (B,S,w); hist (B,CONV_W-1,w) from the
    previous segment. Returns (out (B,S,w), new_hist)."""
    full = jnp.concatenate([hist, u], axis=1)  # (B, S+3, w)
    out = jnp.zeros_like(u)
    S = u.shape[1]
    for i in range(CONV_W):
        out = out + full[:, i: i + S, :] * conv_w[CONV_W - 1 - i][None, None, :]
    new_hist = full[:, -(CONV_W - 1):, :]
    return out + conv_b, new_hist


def _gates(p: Dict, u: jax.Array):
    """Returns (a, gated_in) in u's dtype (bf16-safe; the scan carry stays
    f32). Gate math runs in f32 internally."""
    r = jax.nn.sigmoid((dense(u, p["W_a"]) + p["b_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid((dense(u, p["W_i"]) + p["b_i"]).astype(jnp.float32))
    log_a = -DECAY_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-12)) * (
        i * u.astype(jnp.float32))
    return a.astype(u.dtype), gated_in.astype(u.dtype)


def rglru_seq(p: Dict, x: jax.Array, cfg: ModelConfig, state: Dict
              ) -> Tuple[jax.Array, Dict]:
    """Full-sequence Griffin recurrent block. x is the *normed* input."""
    from repro.models.shard_hooks import constrain

    bspec = ("pod", "data")
    u = dense(x, p["W_x"])
    u, new_conv = _conv1d_causal(u, p["conv_w"], p["conv_b"], state["conv"])
    # §Perf: replicate u's width once (ONE bf16 all-gather) so the W_a/W_i
    # gate projections are local column-parallel matmuls — GSPMD otherwise
    # emits a partial-sum all-reduce of (B,S,w) per projection (2-4x the
    # bytes, and f32 on this backend).
    u = constrain(u, bspec, None, None)
    a, gated_in = _gates(p, u)
    # pin the time-scan operands to ONE layout (batch on data, width on
    # model, time replicated): without this GSPMD reshards the carried
    # state every timestep ("involuntary full rematerialization" — §Perf)
    a = constrain(a, bspec, None, "model")
    gated_in = constrain(gated_in, bspec, None, "model")

    def step(h, t):
        h = a[:, t].astype(jnp.float32) * h + \
            gated_in[:, t].astype(jnp.float32)
        return h, h.astype(a.dtype)

    h0 = constrain(state["h"].astype(jnp.float32), bspec, "model")
    new_h, hs = jax.lax.scan(step, h0, jnp.arange(x.shape[1]))
    hs = jnp.moveaxis(hs, 0, 1).astype(x.dtype)  # (B,S,w)
    gate = jax.nn.gelu(dense(x, p["W_gate"]))
    out = dense(gate * hs, p["W_o"])
    return out, {"h": new_h.astype(state["h"].dtype), "conv": new_conv}


def rglru_decode(p: Dict, x: jax.Array, cfg: ModelConfig, state: Dict
                 ) -> Tuple[jax.Array, Dict]:
    """x (B,1,d) normed input; single recurrent step."""
    u = dense(x, p["W_x"])  # (B,1,w)
    full = jnp.concatenate([state["conv"], u], axis=1)  # (B,CONV_W,w)
    u1 = dense(full, p["conv_w"][::-1], "bcw,cw->bw") + p["conv_b"]
    a, gated_in = _gates(p, u1)
    h = a * state["h"].astype(jnp.float32) + gated_in
    gate = jax.nn.gelu(dense(x[:, 0, :], p["W_gate"]))
    out = dense(gate * h.astype(x.dtype), p["W_o"])
    return out[:, None, :], {"h": h.astype(state["h"].dtype),
                             "conv": full[:, 1:, :]}


def rglru_state_init(cfg: ModelConfig, batch: int, dtype) -> Dict:
    w = cfg.rglru_width or cfg.d_model
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, CONV_W - 1, w), dtype)}


def rglru_state_spec(cfg: ModelConfig, batch: int, dtype) -> Dict:
    w = cfg.rglru_width or cfg.d_model
    return {"h": jax.ShapeDtypeStruct((batch, w), jnp.float32),
            "conv": jax.ShapeDtypeStruct((batch, CONV_W - 1, w), dtype)}
