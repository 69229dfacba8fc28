"""Plain reference for dense GQA decoders (Qwen3, ChatGLM3): weights made
from a seed, the forward pass in straightforward ``jax.numpy``, and the
map of those weights into the served program's parameter tree.

Imports nothing of the program. The forward pass runs layer by layer
(one jitted layer applied in a Python loop over the stacked weights) so
that a model that fills the chip's memory still fits beside its
reference, and returns only per-position statistics of the logits, never
the full ``(rows, positions, vocabulary)`` block.

The architecture follows the published ``config.json`` that the
configuration file copies under ``hf_config``:

- RMSNorm before attention and before the MLP, and on the final hidden
  state; ``x / sqrt(mean(x^2) + eps) * scale``;
- grouped-query attention: head ``h`` reads key/value head
  ``h // (heads / kv_heads)``; causal softmax over ``q.k / sqrt(hd)``;
- Qwen3 also RMS-normalises each head of ``q`` and ``k`` before the
  rotary embedding (``q_norm``, ``k_norm``);
- rotary embedding at base ``theta`` on the first ``rotary_dim`` dims of
  each head, pairing dim ``i`` with ``i + rotary_dim / 2``
  (``rotate_half``). Qwen3 rotates every dim. ChatGLM3 rotates the first
  half of each head; its published code pairs adjacent dims ``(2i,
  2i+1)`` there, a fixed permutation of the query and key projection
  columns that random weights cannot tell apart, and the configuration
  lists it under ``assumed``;
- SwiGLU MLP ``(silu(x Wg) * (x Wu)) Wd``;
- output head tied to the embedding (Qwen3) or separate (ChatGLM3).

Departures from the published models are the configuration file's
``assumed`` list (ChatGLM3's QKV bias is not served).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: standard deviation of the embedding and untied head at init
EMBED_STD = 0.02


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The shapes and constants of a configuration, under one set of
    names, from its family's published keys."""
    hc = config["hf_config"]
    fam = config["family"]
    if fam == "qwen3":
        hd = hc["head_dim"]
        return {"d": hc["hidden_size"], "layers": hc["num_hidden_layers"],
                "heads": hc["num_attention_heads"],
                "kv_heads": hc["num_key_value_heads"], "head_dim": hd,
                "ffn": hc["intermediate_size"], "vocab": hc["vocab_size"],
                "eps": hc["rms_norm_eps"], "theta": float(hc["rope_theta"]),
                "rotary_dim": hd, "qk_norm": True,
                "tied": bool(hc["tie_word_embeddings"]),
                "dtype": hc["torch_dtype"]}
    if fam == "chatglm3":
        hd = hc["kv_channels"]
        return {"d": hc["hidden_size"], "layers": hc["num_layers"],
                "heads": hc["num_attention_heads"],
                "kv_heads": hc["multi_query_group_num"], "head_dim": hd,
                "ffn": hc["ffn_hidden_size"],
                "vocab": hc["padded_vocab_size"],
                "eps": hc["layernorm_epsilon"],
                "theta": 10000.0 * hc.get("rope_ratio", 1.0),
                "rotary_dim": hd // 2, "qk_norm": False,
                "tied": bool(hc.get("tie_word_embeddings", False)),
                "dtype": hc["torch_dtype"]}
    raise ValueError(f"dense_decoder has no family {fam!r}")


def program_config(s: Dict[str, Any]) -> Dict[str, Any]:
    """What the served program's model config must say for these sizes
    (checked at set-up, so a config that drifts from its source fails
    before any request)."""
    return {"d_model": s["d"], "n_layers": s["layers"],
            "n_heads": s["heads"], "n_kv_heads": s["kv_heads"],
            "head_dim": s["head_dim"], "d_ff": s["ffn"],
            "vocab_size": s["vocab"], "tie_embeddings": s["tied"],
            "qk_norm": s["qk_norm"],
            "rope": "rope" if s["rotary_dim"] == s["head_dim"] else "rope2d",
            "rope_theta": s["theta"], "activation": "silu",
            "norm": "rmsnorm"}


# ---------------------------------------------------------------- weights
def init_weights(s: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """Every weight from ``seed`` in one jitted call, on the device, in
    ``dtype``. Layers are stacked on a leading axis."""
    L, d, hd, H, KV, F, V = (s["layers"], s["d"], s["head_dim"], s["heads"],
                             s["kv_heads"], s["ffn"], s["vocab"])
    shapes = {"embed": ((V, d), EMBED_STD),
              "wq": ((L, d, H * hd), d ** -0.5),
              "wk": ((L, d, KV * hd), d ** -0.5),
              "wv": ((L, d, KV * hd), d ** -0.5),
              "wo": ((L, H * hd, d), (H * hd) ** -0.5),
              "w_gate": ((L, d, F), d ** -0.5),
              "w_up": ((L, d, F), d ** -0.5),
              "w_down": ((L, F, d), F ** -0.5)}
    if not s["tied"]:
        shapes["lm_head"] = ((d, V), EMBED_STD)
    ones = {"ln1": (L, d), "ln2": (L, d), "final_norm": (d,)}
    if s["qk_norm"]:
        ones.update({"q_norm": (L, hd), "k_norm": (L, hd)})

    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {name: (jax.random.normal(k, shape, jnp.float32) * std
                      ).astype(dtype)
               for k, (name, (shape, std)) in zip(keys, sorted(shapes.items()))}
        out.update({name: jnp.ones(shape, dtype)
                    for name, shape in ones.items()})
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % 2 ** 32))


def program_tree(w: Dict[str, Any], s: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays in the served program's parameter layout
    (``repro.models.transformer.init_params`` for a one-kind block
    pattern): the run checks this tree's structure, shapes and dtypes
    against the program's own before it hands it over."""
    attn = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if s["qk_norm"]:
        attn["q_norm"] = {"scale": w["q_norm"]}
        attn["k_norm"] = {"scale": w["k_norm"]}
    unit = {"attn_norm": {"scale": w["ln1"]}, "attn": attn,
            "ffn_norm": {"scale": w["ln2"]},
            "ffn": {"w_up": w["w_up"], "w_down": w["w_down"],
                    "w_gate": w["w_gate"]}}
    tree = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "units": (unit,)}
    if not s["tied"]:
        tree["lm_head"] = w["lm_head"]
    return tree


# ---------------------------------------------------------------- forward
def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    out = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, rot, theta):
    """Rotate the first ``rot`` dims of x (B,S,H,hd), pairs (i, i+rot/2)."""
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rot].astype(jnp.float32)
    rotd = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rotd.astype(x.dtype), x[..., rot:]], -1)


class Reference:
    """The forward pass at one precision: ``dtype`` for weights and
    activations, ``precision`` for every matrix product, and, where
    ``operands`` names a narrower type, every product's inputs rounded to
    it first. The reference is float32 at the highest precision; the
    control rounds the products' inputs one step below what the
    configuration states (``check.py``)."""

    def __init__(self, s: Dict[str, Any], dtype=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST, operands=None):
        self.s, self.dtype, self.prec = s, dtype, precision
        self.operands = operands
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)
        self._embed = jax.jit(lambda e, t: e[t].astype(self.dtype))

    def _in(self, x):
        """A matrix product's input, as this precision rounds it."""
        if self.operands is not None:
            x = x.astype(self.operands)
        return x.astype(self.dtype)

    def _mm(self, a, b):
        return jnp.matmul(self._in(a), self._in(b), precision=self.prec)

    def _layer_fn(self, w, l, x):
        s = self.s
        B, S, _ = x.shape
        H, KV, hd = s["heads"], s["kv_heads"], s["head_dim"]
        g = lambda name: jax.lax.dynamic_index_in_dim(  # noqa: E731
            w[name], l, keepdims=False)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        h = _rms(x, g("ln1"), s["eps"])
        q = self._mm(h, g("wq")).reshape(B, S, H, hd)
        k = self._mm(h, g("wk")).reshape(B, S, KV, hd)
        v = self._mm(h, g("wv")).reshape(B, S, KV, hd)
        if s["qk_norm"]:
            q = _rms(q, g("q_norm"), s["eps"])
            k = _rms(k, g("k_norm"), s["eps"])
        q = _rope(q, pos, s["rotary_dim"], s["theta"])
        k = _rope(k, pos, s["rotary_dim"], s["theta"])
        qg = q.reshape(B, S, KV, H // KV, hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", self._in(qg), self._in(k),
                            precision=self.prec).astype(jnp.float32)
        scores = scores / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1).astype(self.dtype)
        att = jnp.einsum("bkgst,btkh->bskgh", self._in(probs), self._in(v),
                         precision=self.prec)
        x = x + self._mm(att.reshape(B, S, H * hd), g("wo"))
        h = _rms(x, g("ln2"), s["eps"])
        y = jax.nn.silu(self._mm(h, g("w_gate"))) * self._mm(h, g("w_up"))
        return x + self._mm(y, g("w_down"))

    def _head_fn(self, w, x, query):
        """Per position: the largest logit, the logit of ``query`` and
        the argmax, all from float32 logits."""
        h = _rms(x, w["final_norm"], self.s["eps"])
        head = w["embed"].T if self.s["tied"] else w["lm_head"]
        logits = self._mm(h, head).astype(jnp.float32)
        at = jnp.take_along_axis(logits, query[..., None], -1)[..., 0]
        return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)

    def hidden(self, w, tokens: np.ndarray):
        """Final hidden states of ``tokens`` (B, S)."""
        x = self._embed(w["embed"], jnp.asarray(tokens))
        for l in range(self.s["layers"]):
            x = self._layer(w, jnp.int32(l), x)
        return x

    def stats(self, w, x, query: np.ndarray):
        return [np.asarray(a) for a in
                self._head(w, x, jnp.asarray(query, jnp.int32))]


def blocks(seqs: List[np.ndarray], length: int, rows: int):
    """Sequences right-padded with 0 to ``length``, ``rows`` at a time
    (right padding never reaches a causal position before it)."""
    for i in range(0, len(seqs), rows):
        chunk = seqs[i:i + rows]
        out = np.zeros((rows, length), np.int32)
        for j, seq in enumerate(chunk):
            out[j, :len(seq)] = seq
        yield i, len(chunk), out
