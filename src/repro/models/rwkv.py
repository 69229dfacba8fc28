"""RWKV-6 ("Finch") block: data-dependent-decay linear attention.

Per head with key/value dim ``hd``::

    out_t  = r_t^T (state_t + diag(u) k_t v_t^T)
    state_{t+1} = diag(w_t) state_t + k_t v_t^T

where the decay ``w_t`` and the token-shift interpolation weights are
data-dependent through low-rank adapters (the RWKV-6 novelty vs RWKV-5).
The sequence form here is a jnp ``lax.scan`` reference; the TPU hot path is
``repro.kernels.rwkv6_scan`` (chunked, state carried in VMEM scratch).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import apply_norm, dense, dense_init, norm_init

LORA_DIM = 32
MIX_NAMES = ("r", "k", "v", "w", "g")


def rwkv_init(rng, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    hd = cfg.rwkv_head_size
    H = d // hd
    ks = iter(jax.random.split(rng, 24))
    tm: Dict = {"norm": norm_init(d, cfg.norm, dtype)}
    tm["mu_x"] = jnp.zeros((d,), dtype)
    for nm in MIX_NAMES:
        tm[f"mu_{nm}"] = jnp.zeros((d,), dtype)
        tm[f"A_{nm}"] = dense_init(next(ks), d, LORA_DIM, dtype, scale=0.01)
        tm[f"B_{nm}"] = dense_init(next(ks), LORA_DIM, d, dtype, scale=0.01)
    for nm in ("r", "k", "v", "g", "o"):
        tm[f"W_{nm}"] = dense_init(next(ks), d, d, dtype)
    # decay base: initialised so w ~ exp(-exp(.)) spans (0, 1) across channels
    decay_span = jnp.linspace(-6.0, 1.0, d, dtype=jnp.float32)
    tm["w_base"] = decay_span.astype(dtype)
    tm["u"] = (jax.random.normal(next(ks), (H, hd), jnp.float32) * 0.1
               ).astype(dtype)
    tm["ln_x"] = norm_init(hd, "rmsnorm", dtype)  # per-head output norm

    cm: Dict = {"norm": norm_init(d, cfg.norm, dtype)}
    cm["mu_k"] = jnp.zeros((d,), dtype)
    cm["mu_r"] = jnp.zeros((d,), dtype)
    cm["W_k"] = dense_init(next(ks), d, cfg.d_ff, dtype)
    cm["W_v"] = dense_init(next(ks), cfg.d_ff, d, dtype)
    cm["W_r"] = dense_init(next(ks), d, d, dtype)
    return {"time_mix": tm, "channel_mix": cm}


def _token_shift(x: jax.Array, last: jax.Array) -> jax.Array:
    """x (B,S,d), last (B,d) = final token of the previous segment."""
    return jnp.concatenate([last[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(tm: Dict, x, xx, nm: str) -> jax.Array:
    """RWKV-6 data-dependent lerp between x and shifted x."""
    base = x + xx * tm["mu_x"]
    lora = dense(jnp.tanh(dense(base, tm[f"A_{nm}"])), tm[f"B_{nm}"])
    return x + xx * (tm[f"mu_{nm}"] + lora)


def _rkvwg(tm: Dict, x: jax.Array, shifted: jax.Array, H: int, hd: int):
    xx = shifted - x
    r = dense(_ddlerp(tm, x, xx, "r"), tm["W_r"])
    k = dense(_ddlerp(tm, x, xx, "k"), tm["W_k"])
    v = dense(_ddlerp(tm, x, xx, "v"), tm["W_v"])
    g = jax.nn.silu(dense(_ddlerp(tm, x, xx, "g"), tm["W_g"]))
    w_in = _ddlerp(tm, x, xx, "w")
    lora_w = dense(jnp.tanh(dense(w_in, tm["A_w"])), tm["B_w"])
    log_w = tm["w_base"].astype(jnp.float32) + lora_w.astype(jnp.float32)
    w = jnp.exp(-jnp.exp(log_w))  # (…, d) in (0,1)
    shp = x.shape[:-1] + (H, hd)
    return (r.reshape(shp), k.reshape(shp), v.reshape(shp),
            w.reshape(shp), g)


def _wkv_step(state, rkvw):
    """state (B,H,hd,hd); r/k/v/w (B,H,hd) for one timestep."""
    r, k, v, w, u = rkvw
    kv = k[..., :, None] * v[..., None, :]  # (B,H,hd,hd)
    out = jnp.einsum("bhi,bhij->bhj", r, state + u[None, :, :, None] * kv)
    new_state = w[..., :, None] * state + kv
    return new_state, out


def time_mix_seq(tm: Dict, x: jax.Array, cfg: ModelConfig,
                 state: jax.Array, shift: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence time-mix. x (B,S,d); state (B,H,hd,hd); shift (B,d).

    Returns (out (B,S,d), new_state, new_shift).
    """
    B, S, d = x.shape
    hd = cfg.rwkv_head_size
    H = d // hd
    shifted = _token_shift(x, shift)
    r, k, v, w, g = _rkvwg(tm, x, shifted, H, hd)
    u = tm["u"].astype(jnp.float32)
    # NOTE (§Perf iteration 6): pinning the scan operands (state sharded on
    # the value dim) was tried and MEASURED WORSE (19.4s vs 14.4s of
    # collectives on train_4k) — GSPMD's own layout for the WKV scan beats
    # the hand-chosen one; constraints reverted.

    def step(st, t):
        return _wkv_step(st, (r[:, t].astype(jnp.float32),
                              k[:, t].astype(jnp.float32),
                              v[:, t].astype(jnp.float32),
                              w[:, t].astype(jnp.float32), u))

    new_state, outs = jax.lax.scan(step, state.astype(jnp.float32),
                                   jnp.arange(S))
    out = jnp.moveaxis(outs, 0, 1)  # (B,S,H,hd)
    out = apply_norm(tm["ln_x"], out.astype(x.dtype), "rmsnorm")
    out = dense(out.reshape(B, S, d) * g, tm["W_o"])
    return out, new_state.astype(state.dtype), x[:, -1, :]


def time_mix_decode(tm: Dict, x: jax.Array, cfg: ModelConfig,
                    state: jax.Array, shift: jax.Array):
    """Single-token decode. x (B,1,d)."""
    B, _, d = x.shape
    hd = cfg.rwkv_head_size
    H = d // hd
    xt = x[:, 0, :]
    r, k, v, w, g = _rkvwg(tm, xt, shift, H, hd)
    u = tm["u"].astype(jnp.float32)
    new_state, out = _wkv_step(state.astype(jnp.float32),
                               (r.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32), w.astype(jnp.float32),
                                u))
    out = apply_norm(tm["ln_x"], out[:, :, None, :].swapaxes(1, 2
                     ).astype(x.dtype), "rmsnorm")  # (B,1,H,hd)
    out = dense(out.reshape(B, 1, d) * g[:, None, :], tm["W_o"])
    return out, new_state.astype(state.dtype), xt


def channel_mix(cm: Dict, x: jax.Array, shift: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) (S may be 1); returns (out, new_shift)."""
    shifted = _token_shift(x, shift)
    xx = shifted - x
    xk = x + xx * cm["mu_k"]
    xr = x + xx * cm["mu_r"]
    k = jnp.square(jax.nn.relu(dense(xk, cm["W_k"])))
    out = jax.nn.sigmoid(dense(xr, cm["W_r"])) * dense(k, cm["W_v"])
    return out, x[:, -1, :]


def rwkv_state_init(cfg: ModelConfig, batch: int, dtype) -> Dict:
    d = cfg.d_model
    hd = cfg.rwkv_head_size
    H = d // hd
    return {
        "att_state": jnp.zeros((batch, H, hd, hd), jnp.float32),
        "att_shift": jnp.zeros((batch, d), dtype),
        "ffn_shift": jnp.zeros((batch, d), dtype),
    }


def rwkv_state_spec(cfg: ModelConfig, batch: int, dtype) -> Dict:
    d = cfg.d_model
    hd = cfg.rwkv_head_size
    H = d // hd
    return {
        "att_state": jax.ShapeDtypeStruct((batch, H, hd, hd), jnp.float32),
        "att_shift": jax.ShapeDtypeStruct((batch, d), dtype),
        "ffn_shift": jax.ShapeDtypeStruct((batch, d), dtype),
    }


def rwkv_block(p: Dict, x: jax.Array, cfg: ModelConfig, state: Dict,
               decode: bool, norm_kind: str) -> Tuple[jax.Array, Dict]:
    """Residual RWKV block (time-mix + channel-mix)."""
    h = apply_norm(p["time_mix"]["norm"], x, norm_kind)
    fn = time_mix_decode if decode else time_mix_seq
    att, new_att_state, new_att_shift = fn(
        p["time_mix"], h, cfg, state["att_state"], state["att_shift"])
    x = x + att
    h = apply_norm(p["channel_mix"]["norm"], x, norm_kind)
    ffn, new_ffn_shift = channel_mix(p["channel_mix"], h, state["ffn_shift"])
    x = x + ffn
    return x, {"att_state": new_att_state, "att_shift": new_att_shift,
               "ffn_shift": new_ffn_shift}
