"""Plain reference for ChatGLM3-6B as published: weights made from a seed
in the published layout, the forward pass of ``modeling_chatglm.py`` in
straightforward ``jax.numpy``, and the map of those weights into the
served program's parameter tree, as loading a real checkpoint would do.

Imports nothing of the program; takes the precision scaffolding of
its ``Reference`` (``_in``, ``_mm``), ``_rms`` and ``blocks`` from its
sibling ``dense_decoder.py``. The layer equations follow
``modeling_chatglm.py`` (THUDM/chatglm3-6b), for the keys of the
configuration file's ``hf_config``:

- RMSNorm ``x * rsqrt(mean(x^2) + layernorm_epsilon) * weight`` before
  attention (``input_layernorm``), before the MLP
  (``post_attention_layernorm``) and on the final hidden state
  (``final_layernorm``, ``post_layer_norm``); residuals around both
  (``apply_residual_connection_post_layernorm`` false);
- one fused ``query_key_value`` projection of width ``(heads + 2 *
  multi_query_group_num) * kv_channels`` with a bias (``add_qkv_bias``),
  its output split into q (``heads`` heads), k and v
  (``multi_query_group_num`` heads each); query head ``h`` reads key and
  value group ``h // (heads / groups)``;
- rotary embedding at base ``10000 * rope_ratio`` on the first
  ``kv_channels / 2`` dims of each head of q and k, pairing ADJACENT
  dims ``(2i, 2i + 1)`` with frequency ``i`` (``apply_rotary_pos_emb``);
  the other dims pass through;
- causal softmax over ``q.k / sqrt(kv_channels)`` in float32
  (``apply_query_key_layer_scaling`` divides the scores by the layer
  number and ``attention_softmax_in_fp32`` multiplies it back, so the
  scale is unchanged), then the ``dense`` output projection, no bias;
- a fused ``dense_h_to_4h`` of width ``2 * ffn_hidden_size``, split in
  halves as ``silu(first) * second`` (``swiglu``), then ``dense_4h_to_h``;
- an untied ``output_layer``.

The program rotates the pairs ``(i, i + rotary_dim / 2)`` of a head
(``rope2d``), so ``program_tree`` permutes the rotary columns of the q
and k projections and of their biases, per head: program column ``j <
rotary_dim / 2`` takes published column ``2j``, column ``rotary_dim / 2
<= j < rotary_dim`` takes ``2(j - rotary_dim / 2) + 1``, the rest stay.
q.k is unchanged by a permutation applied to both, and v is not rotated,
so the program computes the published model; a wrong permutation does
not.

Memory: the two fused matrices of every layer (``query_key_value`` and
``dense_h_to_4h``, about half the layer weights) are never held whole.
Each layer's pair is drawn from the seed when it is needed, by one
jitted function: ``program_tree`` draws them to split and permute them
into the served tree, and the forward pass draws them again, bit for
bit. Every other weight is one array, shared by the served tree and the
reference, so the model and its reference fit the chip together. The
forward pass runs layer by layer and returns only per-position
statistics of the logits (``logits`` gives them whole, for tests at
small widths).
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_dense_decoder",
    Path(__file__).with_name("dense_decoder.py"))
dense_decoder = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense_decoder)

_rms, blocks = dense_decoder._rms, dense_decoder.blocks
#: standard deviation of the embedding and the output layer at init
EMBED_STD = dense_decoder.EMBED_STD
#: standard deviation of the query_key_value bias: the scale of the
#: projection's output (unit-RMS input times N(0, 1/fan_in) weights), so
#: the bias moves the logits as much as the product does
BIAS_STD = 1.0


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The shapes and constants of the configuration, under the names
    ``bench/work.py`` reads, from the published keys. Refuses a
    configuration whose architecture this reference does not compute."""
    hc = config["hf_config"]
    want = {"rmsnorm": True, "post_layer_norm": True,
            "add_bias_linear": False,
            "apply_residual_connection_post_layernorm": False,
            "tie_word_embeddings": False}
    off = {k: hc.get(k) for k, v in want.items() if hc.get(k, v) != v}
    if off:
        raise ValueError(f"chatglm3 reference does not compute {off}")
    hd = hc["kv_channels"]
    heads = hc["num_attention_heads"]
    return {"d": hc["hidden_size"], "layers": hc["num_layers"],
            "heads": heads,
            "kv_heads": (hc["multi_query_group_num"]
                         if hc["multi_query_attention"] else heads),
            "head_dim": hd, "ffn": hc["ffn_hidden_size"],
            "vocab": hc["padded_vocab_size"],
            "eps": float(hc["layernorm_epsilon"]),
            "theta": 10000.0 * hc.get("rope_ratio", 1.0),
            "rotary_dim": hd // 2, "qk_norm": False,
            "qkv_bias": bool(hc["add_qkv_bias"]), "tied": False,
            "dtype": hc["torch_dtype"]}


def program_config(s: Dict[str, Any]) -> Dict[str, Any]:
    """What the served program's model config must say for these sizes
    (checked at set-up, so a config that drifts from its source fails
    before any request)."""
    return {"d_model": s["d"], "n_layers": s["layers"],
            "n_heads": s["heads"], "n_kv_heads": s["kv_heads"],
            "head_dim": s["head_dim"], "d_ff": s["ffn"],
            "vocab_size": s["vocab"], "tie_embeddings": False,
            "qk_norm": False, "qkv_bias": s["qkv_bias"],
            "norm_eps": s["eps"], "rope": "rope2d",
            "rope_theta": s["theta"], "activation": "silu",
            "norm": "rmsnorm"}


def _qkv_width(s: Dict[str, Any]) -> int:
    return (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"]


# ---------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnames=("d", "qkv", "h4", "dtype"))
def _draw_fused(key, layer, *, d, qkv, h4, dtype):
    """Layer ``layer``'s ``query_key_value`` (d, qkv) and
    ``dense_h_to_4h`` (d, h4) weights, stored (in, out)."""
    kq, kh = jax.random.split(jax.random.fold_in(key, layer))
    std = d ** -0.5
    return ((jax.random.normal(kq, (d, qkv), jnp.float32) * std
             ).astype(dtype),
            (jax.random.normal(kh, (d, h4), jnp.float32) * std
             ).astype(dtype))


def fused_layer(w: Dict[str, Any], s: Dict[str, Any], layer: int):
    """The fused weights of one layer, drawn from the seed."""
    return _draw_fused(w["fused_key"], jnp.int32(layer), d=s["d"],
                       qkv=_qkv_width(s), h4=2 * s["ffn"],
                       dtype=jnp.dtype(w["dtype"]))


def init_weights(s: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """Every weight but the fused ones from ``seed``, in one jitted call,
    on the device, in ``dtype``; layers stacked on a leading axis, each
    matrix stored (in, out). The fused ones come from ``fused_layer``."""
    L, d, hd, H, F, V = (s["layers"], s["d"], s["head_dim"], s["heads"],
                         s["ffn"], s["vocab"])
    shapes = {"word_embeddings": ((V, d), EMBED_STD),
              "output_layer": ((d, V), EMBED_STD),
              "dense": ((L, H * hd, d), (H * hd) ** -0.5),
              "dense_4h_to_h": ((L, F, d), F ** -0.5)}
    if s["qkv_bias"]:
        shapes["query_key_value.bias"] = ((L, _qkv_width(s)), BIAS_STD)
    ones = {"input_layernorm": (L, d), "post_attention_layernorm": (L, d),
            "final_layernorm": (d,)}

    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {name: (jax.random.normal(k, shape, jnp.float32) * std
                      ).astype(dtype)
               for k, (name, (shape, std)) in zip(keys, sorted(shapes.items()))}
        out.update({name: jnp.ones(shape, dtype)
                    for name, shape in ones.items()})
        return out

    key, fused_key = jax.random.split(jax.random.PRNGKey(seed % 2 ** 32))
    w = jax.jit(make)(key)
    w.update(fused_key=fused_key, dtype=jnp.dtype(dtype).name)
    return w


def rotary_order(head_dim: int, rotary_dim: int) -> np.ndarray:
    """For each column of a head in the program's layout, the published
    column it takes: the adjacent pairs (2i, 2i+1) become (i, i + half)."""
    half = rotary_dim // 2
    j = np.arange(head_dim)
    return np.where(j < half, 2 * j,
                    np.where(j < rotary_dim, 2 * (j - half) + 1, j))


def _per_head(x, order, heads: int):
    """Columns (..., heads * hd) with each head's reordered."""
    hd = order.shape[0]
    y = x.reshape(x.shape[:-1] + (heads, hd))[..., order]
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "ffn"),
                   donate_argnums=(0,))
def _place(parts, qkv_w, h4_w, layer, order, *, heads, kv_heads, ffn):
    """Split one layer's fused weights into the program's stacked
    arrays ``parts`` (written in place)."""
    hd = order.shape[0]
    q, k, v = jnp.split(qkv_w, [heads * hd, (heads + kv_heads) * hd], -1)
    new = {"wq": _per_head(q, order, heads), "wk": _per_head(k, order,
                                                             kv_heads),
           "wv": v, "w_gate": h4_w[:, :ffn], "w_up": h4_w[:, ffn:]}
    return {n: jax.lax.dynamic_update_index_in_dim(parts[n], new[n], layer, 0)
            for n in parts}


def program_tree(w: Dict[str, Any], s: Dict[str, Any]) -> Dict[str, Any]:
    """The weights in the served program's parameter layout
    (``repro.models.transformer.init_params``, one-kind block pattern):
    the fused matrices split, the rotary columns of q and k permuted
    (``rotary_order``); the run checks this tree's structure, shapes and
    dtypes against the program's own before it hands it over."""
    L, d, hd, H, KV, F = (s["layers"], s["d"], s["head_dim"], s["heads"],
                          s["kv_heads"], s["ffn"])
    order = jnp.asarray(rotary_order(hd, s["rotary_dim"]), jnp.int32)
    dtype = jnp.dtype(w["dtype"])
    parts = {"wq": jnp.zeros((L, d, H * hd), dtype),
             "wk": jnp.zeros((L, d, KV * hd), dtype),
             "wv": jnp.zeros((L, d, KV * hd), dtype),
             "w_gate": jnp.zeros((L, d, F), dtype),
             "w_up": jnp.zeros((L, d, F), dtype)}
    for layer in range(L):
        qkv_w, h4_w = fused_layer(w, s, layer)
        parts = _place(parts, qkv_w, h4_w, jnp.int32(layer), order,
                       heads=H, kv_heads=KV, ffn=F)
        del qkv_w, h4_w
    attn = {"wq": parts["wq"], "wk": parts["wk"], "wv": parts["wv"],
            "wo": w["dense"]}
    if s["qkv_bias"]:
        bq, bk, bv = jnp.split(w["query_key_value.bias"],
                               [H * hd, (H + KV) * hd], -1)
        attn.update(bq=_per_head(bq, order, H), bk=_per_head(bk, order, KV),
                    bv=bv)
    unit = {"attn_norm": {"scale": w["input_layernorm"]}, "attn": attn,
            "ffn_norm": {"scale": w["post_attention_layernorm"]},
            "ffn": {"w_up": parts["w_up"], "w_down": w["dense_4h_to_h"],
                    "w_gate": parts["w_gate"]}}
    return {"embed": w["word_embeddings"],
            "final_norm": {"scale": w["final_layernorm"]},
            "units": (unit,), "lm_head": w["output_layer"]}


# ---------------------------------------------------------------- forward
def _rotary(x, pos, rot, theta):
    """``apply_rotary_pos_emb``: x (B,S,heads,hd); the first ``rot`` dims
    rotate as adjacent pairs (2i, 2i+1) at frequency i."""
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv  # (B,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x[..., :rot].astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    out = out.reshape(x.shape[:-1] + (rot,)).astype(x.dtype)
    return jnp.concatenate([out, x[..., rot:]], -1)


#: the stacked per-layer weights the forward pass reads from ``w``
_LAYER_KEYS = ("input_layernorm", "post_attention_layernorm", "dense",
               "dense_4h_to_h", "query_key_value.bias")


class Reference(dense_decoder.Reference):
    """``dense_decoder.Reference``'s precisions (``dtype``, ``precision``,
    ``operands``) over ChatGLM3's layer equations and weight layout."""

    def __init__(self, s: Dict[str, Any], dtype=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST, operands=None):
        super().__init__(s, dtype, precision, operands)
        self._logits = jax.jit(self._logits_fn)

    def _layer_fn(self, lay, l, x, qkv_w, h4_w):
        s = self.s
        B, S, _ = x.shape
        H, KV, hd, F = s["heads"], s["kv_heads"], s["head_dim"], s["ffn"]
        g = lambda name: jax.lax.dynamic_index_in_dim(  # noqa: E731
            lay[name], l, keepdims=False).astype(self.dtype)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        # self-attention
        h = _rms(x, g("input_layernorm"), s["eps"])
        mixed = self._mm(h, qkv_w)
        if s["qkv_bias"]:
            mixed = mixed + g("query_key_value.bias")
        q, k, v = jnp.split(mixed, [H * hd, (H + KV) * hd], -1)
        q = _rotary(q.reshape(B, S, H, hd), pos, s["rotary_dim"], s["theta"])
        k = _rotary(k.reshape(B, S, KV, hd), pos, s["rotary_dim"],
                    s["theta"])
        v = v.reshape(B, S, KV, hd)
        # each group's key and value serve heads / groups query heads
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        scores = jnp.einsum("bshd,bthd->bhst", self._in(q), self._in(k),
                            precision=self.prec).astype(jnp.float32)
        scores = scores / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1).astype(self.dtype)
        ctx = jnp.einsum("bhst,bthd->bshd", self._in(probs), self._in(v),
                         precision=self.prec)
        x = x + self._mm(ctx.reshape(B, S, H * hd), g("dense"))
        # MLP
        h = _rms(x, g("post_attention_layernorm"), s["eps"])
        gate, up = jnp.split(self._mm(h, h4_w), [F], -1)
        return x + self._mm(jax.nn.silu(gate) * up, g("dense_4h_to_h"))

    def _logits_fn(self, w, x):
        h = _rms(x, w["final_layernorm"], self.s["eps"])
        return self._mm(h, w["output_layer"]).astype(jnp.float32)

    def _head_fn(self, w, x, query):
        """Per position: the largest logit, the logit of ``query`` and
        the argmax, all from float32 logits."""
        logits = self._logits_fn(w, x)
        at = jnp.take_along_axis(logits, query[..., None], -1)[..., 0]
        return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)

    @staticmethod
    def _head_weights(w):
        return {k: w[k] for k in ("final_layernorm", "output_layer")}

    def hidden(self, w, tokens: np.ndarray):
        """Final hidden states of ``tokens`` (B, S), before the final
        norm."""
        lay = {k: w[k] for k in _LAYER_KEYS if k in w}
        x = self._embed(w["word_embeddings"], jnp.asarray(tokens))
        for l in range(self.s["layers"]):
            qkv_w, h4_w = fused_layer(w, self.s, l)
            x = self._layer(lay, jnp.int32(l), x, qkv_w, h4_w)
        return x

    def stats(self, w, x, query: np.ndarray):
        return [np.asarray(a) for a in
                self._head(self._head_weights(w), x,
                           jnp.asarray(query, jnp.int32))]

    def logits(self, w, x) -> np.ndarray:
        """Float32 logits (B, S, vocab) of hidden states ``x``."""
        return np.asarray(self._logits(self._head_weights(w), x))

