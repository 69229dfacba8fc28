"""Sharding rules: parameter/cache/input PartitionSpecs for the 2D/3D mesh.

Baseline layout (the §Perf hillclimb iterates from here):
* tensor parallelism over the ``model`` axis — attention/MLP projections
  column-/row-sharded, embeddings vocab-sharded, MoE experts
  expert-parallel over ``model``;
* batch over ``(pod, data)``;
* KV caches: batch on data axes + *cache length* on ``model``: decode
  attention then computes scores locally per length-shard and only psums
  the (B,H) softmax statistics and the (B,H,hd) weighted values — a
  distributed flash-decode. (Sharding n_kv_heads is impossible — kv is
  1..16 and uneven; sharding head_dim would psum (B,H,C) score tensors.)
* recurrent states: RWKV (B,H,hdk,hdv) sharded on the *value* dim so the
  per-step outer-product recurrence is local (decay/bonus contract over
  the key dim); RG-LRU width on ``model`` (diagonal => local).

Rules are path-keyed; stacked scan-unit params carry one extra leading
(n_units) dim which maps to None.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.tree import tree_map_with_path
from repro.config.base import InputShape, ModelConfig

M = "model"


def _base_spec(path: str, eff_ndim: int) -> Tuple:
    """Spec for the *unstacked* trailing dims of a leaf."""
    parts = path.split("/")
    name = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""

    if name == "embed":
        return (M, None)
    if name == "lm_head":
        return (None, M)
    if name in ("wq", "wk", "wv"):
        return (None, M)
    if name in ("bq", "bk", "bv"):                     # output dim of wq/wk/wv
        return (M,)
    if name == "wo":
        return (M, None)
    if name in ("w_up", "w_gate") and eff_ndim == 3:   # MoE experts (E,d,f)
        return (M, None, None)
    if name == "w_down" and eff_ndim == 3:             # MoE experts (E,f,d)
        return (M, None, None)
    if name in ("w_up", "w_gate"):
        return (None, M)
    if name == "w_down":
        return (M, None)
    if name == "router":
        return (None, M)
    # RWKV
    if parent == "time_mix":
        if name in ("W_r", "W_k", "W_v", "W_g"):
            return (None, M)
        if name == "W_o":
            return (M, None)
        if name == "u":
            return (None, M)        # (H, hd): shard hd
        if name == "w_base":
            return (M,)
    if parent == "channel_mix":
        if name in ("W_k", "W_r"):
            return (None, M)
        if name == "W_v":
            return (M, None)
    # RG-LRU
    if name in ("W_x",) or (parent == "rec" and name == "W_gate"):
        return (None, M)
    if name in ("W_a", "W_i"):
        return (None, M)
    if parent == "rec" and name == "W_o":
        return (M, None)
    if name == "conv_w":
        return (None, M)
    if name in ("conv_b", "lam", "b_a", "b_i"):
        return (M,)
    if name == "frontend_proj":
        return (None, M)
    # norms, biases, loras, mu_*: replicate
    return tuple(None for _ in range(eff_ndim))


def param_pspec(path: str, leaf, mode: str = "tp",
                data_size: int = 16) -> P:
    parts = path.split("/")
    # stacked scan-unit params: "units/<pos>/..." carries a leading dim
    stacked = 1 if parts[0] == "units" or (
        parts[0] == "encoder" and parts[1] == "layers") else 0
    eff_ndim = leaf.ndim - stacked
    spec = _base_spec(path, eff_ndim)
    if len(spec) != eff_ndim:  # rule mismatch -> replicate (safe default)
        spec = tuple(None for _ in range(eff_ndim))
    if mode == "2d" and eff_ndim >= 2:
        # §Perf iteration: additionally shard the other weight dim over
        # `data` (2D weight sharding). XLA/GSPMD then picks per-use between
        # gathering the weight (FSDP-style, good for big-token steps) and
        # partial contraction + reduce (2D TP, good for decode). Only
        # upgrade a dim whose size divides the data axis.
        spec_l = list(spec)
        for i, s in enumerate(spec_l):
            dim = leaf.shape[stacked + i]
            if s is None and dim % data_size == 0:
                spec_l[i] = "data"
                break
        spec = tuple(spec_l)
    return P(*((None,) * stacked + spec))


def param_shardings(mesh, params_abstract, mode: str = "tp") -> Any:
    data_size = mesh.shape.get("data", 1)
    return tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_pspec(path, leaf, mode, data_size)),
        params_abstract)


# ---------------------------------------------------------------- inputs
def _batch_spec(mesh, global_batch: int):
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if global_batch % n == 0:
        return tuple(axes)
    return None  # e.g. long_500k batch=1: replicate


def input_shardings(mesh, cfg: ModelConfig, specs: Dict,
                    mode: str = "tp") -> Dict:
    out = {}
    for key, sds in specs.items():
        b_ax = None if mode == "decode2d" else _batch_spec(
            mesh, sds.shape[0])
        trailing = (None,) * (len(sds.shape) - 1)
        out[key] = NamedSharding(mesh, P(b_ax, *trailing))
    return out


# ---------------------------------------------------------------- caches
def cache_pspec(path: str, leaf, mesh, batch_axes,
                mode: str = "tp") -> NamedSharding:
    parts = path.split("/")
    name = parts[-1]
    stacked = 1 if parts[0] == "units" else 0
    b_ax = None if mode == "decode2d" else batch_axes
    # decode2d: batch replicated => shard the cache length over BOTH axes
    len_ax = (tuple(a for a in ("data", M) if a in mesh.axis_names)
              if mode == "decode2d" else M)
    if name in ("k", "v", "ck", "cv"):
        clen = leaf.shape[stacked + 1]
        n_len = 1
        axes = len_ax if isinstance(len_ax, tuple) else (len_ax,)
        for a in axes:
            n_len *= mesh.shape[a]
        spec = (b_ax, len_ax if clen % n_len == 0 else M, None, None)
    elif name == "att_state":
        spec = (b_ax, None, None, M)          # (B, H, hd_k, hd_v): shard v
    elif name in ("att_shift", "ffn_shift", "h"):
        spec = (b_ax, M)                      # (B, d|w)
    elif name == "conv":
        spec = (b_ax, None, M)                # (B, 3, w)
    else:
        spec = tuple(None for _ in range(leaf.ndim - stacked))
    return NamedSharding(mesh, P(*((None,) * stacked + spec)))


def cache_shardings(mesh, cfg: ModelConfig, cache_abstract,
                    global_batch: int, mode: str = "tp") -> Any:
    b_ax = _batch_spec(mesh, global_batch)
    return tree_map_with_path(
        lambda path, leaf: cache_pspec(path, leaf, mesh, b_ax, mode),
        cache_abstract)


def replicated(mesh):
    return NamedSharding(mesh, P())


# ------------------------------------------- serving-engine TP shardings
# The continuous serving engine spans a 1D ("model",)-only mesh carved
# from the pool's shared device set (launch/mesh.make_tp_mesh): slots
# and block tables are replicated, and the model dimension is TENSOR
# parallelism only. Unlike the launch-scale rules above, serving configs
# (tiny test models included) have dims the model axis need not divide —
# odd vocab sizes, 2-head caches on a 4-way mesh — so every spec here is
# filtered through ``_fit_mesh``: a sharded axis that does not divide
# its dim falls back to replicated instead of failing inside jit.

def _fit_mesh(spec: Tuple, shape: Tuple[int, ...], mesh) -> Tuple:
    """Drop spec axes absent from ``mesh`` or not dividing their dim."""
    out = []
    for s, dim in zip(spec, shape):
        keep = (s is not None and s in mesh.axis_names
                and dim % mesh.shape[s] == 0)
        out.append(s if keep else None)
    return tuple(out)


def engine_param_shardings(mesh, params_abstract) -> Any:
    """``param_shardings`` for a serving instance: the launch TP rules,
    divisibility-filtered per leaf (e.g. a 97-entry embedding stays
    replicated on a 2-way mesh while wq/wk/wv/wo shard)."""
    def sharding(path, leaf):
        spec = tuple(param_pspec(path, leaf, mode="tp"))
        return NamedSharding(mesh, P(*_fit_mesh(spec, leaf.shape, mesh)))
    return tree_map_with_path(sharding, params_abstract)


def engine_cache_pspec(path: str, leaf, mesh) -> P:
    """PartitionSpec for one serving-engine cache leaf — block pools
    and per-slot staging caches alike. Linear KV leaves, staging
    ``(B, S, n_kv, hd)`` and pooled ``(n_blocks, bs, n_kv, hd)``, shard
    the HEAD axis over ``model``, matching the column-sharded
    wq/wk/wv: scores and the weighted-value contraction stay local per
    head shard and the row-sharded wo psums once per step. (The
    launch-scale rules shard dense cache LENGTH instead — right for
    16-chip axes where n_kv never divides, wrong here where the block
    axis is gathered through replicated tables and head counts are
    chosen to divide the TP degree.) Recurrent/windowed leaves follow
    the launch rules: att_state value dim, shift/conv width on
    ``model``. Anything not divisible replicates."""
    parts = path.split("/")
    name = parts[-1]
    stacked = 1 if parts[0] == "units" else 0
    if name in ("k", "v", "ck", "cv"):
        spec = (None, None, M, None)
    elif name == "att_state":
        spec = (None, None, None, M)          # (B, H, hd_k, hd_v)
    elif name in ("att_shift", "ffn_shift", "h"):
        spec = (None, M)                      # (B, d|w)
    elif name == "conv":
        spec = (None, None, M)                # (B, 3, w)
    else:
        spec = tuple(None for _ in range(leaf.ndim - stacked))
    spec = _fit_mesh(spec, leaf.shape[stacked:], mesh)
    return P(*((None,) * stacked + spec))


def engine_cache_shardings(mesh, cache_abstract) -> Any:
    return tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, engine_cache_pspec(path, leaf, mesh)),
        cache_abstract)
