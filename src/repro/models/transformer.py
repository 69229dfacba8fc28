"""Composable decoder trunk covering all assigned architecture families.

Layer stack = ``n_units`` repetitions of ``cfg.block_pattern`` executed under
``lax.scan`` (stacked params — keeps HLO size and compile time independent of
depth, MaxText-style) plus an unrolled tail when ``n_layers`` is not a
multiple of the pattern length. Encoder-decoder (seamless) adds a scanned
bidirectional encoder and per-layer cross-attention.

Forward modes:
  * ``loss(params, batch)``        — teacher-forced LM loss (train_4k)
  * ``prefill(params, batch)``     — logits + populated cache (prefill_32k)
  * ``decode_step(params, cache, batch)`` — one token (decode_32k/long_500k)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import InputShape, ModelConfig
from repro.models import attention as attn
from repro.models import rglru as rg
from repro.models import rwkv as rk
from repro.models.layers import (apply_embed, apply_mlp, apply_norm,
                                 dense_init, embed_init, mlp_init, norm_init,
                                 unembed)
from repro.models.moe import moe_apply, moe_init
from repro.models.rope import default_positions, vision_grid_positions

LOSS_CHUNK = 256


# =====================================================================
# parameter construction
# =====================================================================
def _layer_init(rng, cfg: ModelConfig, kind: str, dtype,
                cross: bool) -> Dict:
    ks = iter(jax.random.split(rng, 8))
    if kind == "rwkv":
        return rk.rwkv_init(next(ks), cfg, dtype)
    p: Dict[str, Any] = {}
    if kind == "rglru":
        p["rec"] = rg.rglru_init(next(ks), cfg, dtype)
    else:  # attn | local_attn
        p["attn_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["attn"] = attn.attn_init(next(ks), cfg, dtype)
        if cross:
            p["cross_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
            p["cross"] = attn.attn_init(next(ks), cfg, dtype)
    p["ffn_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if cfg.n_experts and kind != "attn_dense":
        p["ffn"] = moe_init(next(ks), cfg, dtype)
    else:
        width = (cfg.dense_ff or cfg.d_ff) if kind == "attn_dense" else cfg.d_ff
        p["ffn"] = mlp_init(next(ks), cfg.d_model, width,
                            gated=(cfg.activation in ("silu", "geglu")),
                            dtype=dtype)
    return p


def _stack(trees: List[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    ks = iter(jax.random.split(rng, 64))
    kinds = cfg.layer_kinds()
    k = len(cfg.block_pattern)
    n_units = cfg.n_layers // k
    tail_kinds = kinds[n_units * k:]
    p: Dict[str, Any] = {
        "embed": embed_init(next(ks), cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(next(ks), cfg.d_model, cfg.vocab_size,
                                  dtype, scale=0.02)
    cross = cfg.enc_dec
    if n_units:
        units = []
        for pos, kind in enumerate(cfg.block_pattern):
            # one vmapped init per kind (bit-identical to initialising
            # the units one by one and stacking), so the traced program
            # does not grow with depth
            keys = jnp.stack([next(ks) for _ in range(n_units)])
            units.append(jax.vmap(functools.partial(
                _layer_init, cfg=cfg, kind=kind, dtype=dtype,
                cross=cross))(keys))
        p["units"] = tuple(units)
    if tail_kinds:
        p["tail"] = tuple(_layer_init(next(ks), cfg, kind, dtype, cross)
                          for kind in tail_kinds)
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(next(ks), cfg.d_model, cfg.d_model,
                                        dtype)
    if cfg.enc_dec:
        enc_layers = [_layer_init(next(ks), cfg, "attn", dtype, cross=False)
                      for _ in range(cfg.n_enc_layers)]
        p["encoder"] = {"layers": _stack(enc_layers),
                        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype)}
    return p


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda r: init_params(r, cfg, dtype), rng)


# =====================================================================
# single-layer application
# =====================================================================
def _ffn_apply(p, x, cfg: ModelConfig,
               kind: str = "attn") -> Tuple[jax.Array, jax.Array]:
    if cfg.n_experts and kind != "attn_dense":
        y, aux = moe_apply(p, x, cfg)
        return y, aux["lb_loss"] + 1e-3 * aux["z_loss"]
    return apply_mlp(p, x, cfg.activation), jnp.zeros((), jnp.float32)


def _layer_full(p: Dict, x: jax.Array, cfg: ModelConfig, kind: str,
                ctx: Dict) -> Tuple[jax.Array, jax.Array, Dict]:
    """Full-sequence layer. Returns (x, aux_loss, cache_out)."""
    cache_out: Dict = {}
    aux = jnp.zeros((), jnp.float32)
    window = _window_for(cfg, kind)
    if kind == "rwkv":
        state = rk.rwkv_state_init(cfg, x.shape[0], x.dtype)
        x, new_state = rk.rwkv_block(p, x, cfg, state, decode=False,
                                     norm_kind=cfg.norm)
        return x, aux, new_state
    if kind == "rglru":
        state = rg.rglru_state_init(cfg, x.shape[0], x.dtype)
        h = apply_norm(p["rec"]["norm"], x, cfg.norm)
        out, new_state = rg.rglru_seq(p["rec"], h, cfg, state)
        x = x + out
        cache_out = new_state
    else:
        h = apply_norm(p["attn_norm"], x, cfg.norm)
        out = attn.attention_full(
            p["attn"], h, cfg, ctx["positions"], window=window,
            impl=ctx["attn_impl"], chunk=ctx["chunk"],
            mrope_positions=ctx.get("mrope_positions"))
        x = x + out
        if ctx.get("return_cache"):
            cache_out = _prefill_kv(p["attn"], h, cfg, ctx, window)
        if cfg.enc_dec and "cross" in p:
            h = apply_norm(p["cross_norm"], x, cfg.norm)
            out, ck, cv = _cross_full(p["cross"], h, ctx["enc_out"], cfg)
            x = x + out
            if ctx.get("return_cache"):
                cache_out = {**cache_out, "ck": ck, "cv": cv}
    h = apply_norm(p["ffn_norm"], x, cfg.norm)
    y, ffn_aux = _ffn_apply(p["ffn"], h, cfg, kind)
    return x + y, aux + ffn_aux, cache_out


def _layer_decode(p: Dict, x: jax.Array, cfg: ModelConfig, kind: str,
                  cache: Dict, ctx: Dict,
                  layer: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict]:
    """One-token layer. ``layer`` indexes a scan-stacked block pool
    (paged leaves only; see :func:`_trunk_cached`)."""
    window = _window_for(cfg, kind)
    if kind == "rwkv":
        return rk.rwkv_block(p, x, cfg, cache, decode=True,
                             norm_kind=cfg.norm)
    if kind == "rglru":
        h = apply_norm(p["rec"]["norm"], x, cfg.norm)
        out, new_state = rg.rglru_decode(p["rec"], h, cfg, cache)
        x = x + out
        new_cache = new_state
    else:
        h = apply_norm(p["attn_norm"], x, cfg.norm)
        if _paged_here(cfg, kind, ctx):
            # paged layout covers linear KV layers only; ring buffers
            # (windowed) are already bounded by the window and stay dense
            out, kv = attn.attention_decode_paged(
                p["attn"], h, {"k": cache["k"], "v": cache["v"]},
                ctx["block_tables"], ctx["pos"], cfg,
                impl=ctx["attn_impl"], layer=layer)
        else:
            out, kv = attn.attention_decode(
                p["attn"], h, {"k": cache["k"], "v": cache["v"]},
                ctx["pos"], cfg, window=window, impl=ctx["attn_impl"])
        x = x + out
        new_cache = dict(kv)
        if cfg.enc_dec and "cross" in p:
            h = apply_norm(p["cross_norm"], x, cfg.norm)
            out = _cross_cached(p["cross"], h, cache["ck"], cache["cv"], cfg)
            x = x + out
            new_cache["ck"], new_cache["cv"] = cache["ck"], cache["cv"]
    h = apply_norm(p["ffn_norm"], x, cfg.norm)
    y, _ = _ffn_apply(p["ffn"], h, cfg, kind)
    return x + y, new_cache


def _layer_chunk(p: Dict, x: jax.Array, cfg: ModelConfig, kind: str,
                 cache: Dict, ctx: Dict,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict]:
    """Chunked-prefill continuation layer (docs/ARCHITECTURE.md §5):
    process T tokens starting at ``ctx["pos"]`` against a dense decode
    cache. Recurrent layers run their sequence form from the carried
    state; attention layers attend cache + causal chunk prefix.
    ``layer`` as in :func:`_layer_decode`."""
    window = _window_for(cfg, kind)
    if kind == "rwkv":
        return rk.rwkv_block(p, x, cfg, cache, decode=False,
                             norm_kind=cfg.norm)
    if kind == "rglru":
        h = apply_norm(p["rec"]["norm"], x, cfg.norm)
        out, new_state = rg.rglru_seq(p["rec"], h, cfg, cache)
        x = x + out
        new_cache = new_state
    else:
        h = apply_norm(p["attn_norm"], x, cfg.norm)
        if _paged_here(cfg, kind, ctx):
            # paged layout covers linear KV layers only (same gate as
            # _layer_decode): fused chunked prefill and speculative verify
            out, kv = attn.attention_chunk_paged(
                p["attn"], h, {"k": cache["k"], "v": cache["v"]},
                ctx["block_tables"], ctx["pos"], cfg,
                impl=ctx["attn_impl"], layer=layer)
        else:
            out, kv = attn.attention_prefill_chunk(
                p["attn"], h, {"k": cache["k"], "v": cache["v"]},
                ctx["pos"], cfg, window=window, impl=ctx["attn_impl"])
        x = x + out
        new_cache = dict(kv)
    h = apply_norm(p["ffn_norm"], x, cfg.norm)
    y, _ = _ffn_apply(p["ffn"], h, cfg, kind)
    return x + y, new_cache


def _window_for(cfg: ModelConfig, kind: str) -> Optional[int]:
    if kind == "local_attn":
        return cfg.sliding_window or 2048
    return cfg.sliding_window  # dense archs may run windowed (long_500k)


def _prefill_kv(p, h, cfg: ModelConfig, ctx, window) -> Dict:
    """Recompute the rotated K/V for the cache at prefill time."""
    _, k, v = attn._project_qkv(p, h, cfg, ctx["positions"],
                                ctx.get("mrope_positions"))
    if window is not None:
        # ring buffer capacity is ALWAYS the window (decode slot arithmetic
        # is modulo the capacity); keep the last `window` positions
        S = k.shape[1]
        n_keep = min(window, S)
        idx = jnp.arange(S - n_keep, S)
        slots = idx % window
        ring_k = jnp.zeros((k.shape[0], window, cfg.n_kv_heads,
                            cfg.head_dim), k.dtype)
        ring_v = jnp.zeros_like(ring_k)
        ring_k = ring_k.at[:, slots].set(k[:, idx])
        ring_v = ring_v.at[:, slots].set(v[:, idx])
        return {"k": ring_k, "v": ring_v}
    return {"k": k, "v": v}


# ---- cross attention -------------------------------------------------
def _cross_kv(p, enc_out, cfg: ModelConfig):
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def _cross_core(p, x, k, v, cfg: ModelConfig):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    mask = jnp.ones((B, S, k.shape[1]), bool)
    out = attn._sdpa(q, k, v, mask, 1.0 / float(cfg.head_dim) ** 0.5)
    return out.reshape(B, S, -1) @ p["wo"]


def _cross_full(p, x, enc_out, cfg: ModelConfig):
    k, v = _cross_kv(p, enc_out, cfg)
    return _cross_core(p, x, k, v, cfg), k, v


def _cross_cached(p, x, ck, cv, cfg: ModelConfig):
    return _cross_core(p, x, ck, cv, cfg)


# =====================================================================
# trunk
# =====================================================================
def _split_layers(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    k = len(cfg.block_pattern)
    n_units = cfg.n_layers // k
    return n_units, cfg.layer_kinds()[n_units * k:]


def _trunk_full(params: Dict, x: jax.Array, cfg: ModelConfig, ctx: Dict,
                remat: bool) -> Tuple[jax.Array, jax.Array, Dict]:
    n_units, tail_kinds = _split_layers(cfg)
    caches: Dict[str, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)

    if n_units:
        def unit_body(carry, unit_params):
            x, aux = carry
            cache_outs = []
            for pos, kind in enumerate(cfg.block_pattern):
                x, a, c = _layer_full(unit_params[pos], x, cfg, kind, ctx)
                aux = aux + a
                cache_outs.append(c)
            return (x, aux), tuple(cache_outs)

        body = jax.checkpoint(unit_body) if remat else unit_body
        (x, aux_total), unit_caches = jax.lax.scan(
            body, (x, aux_total), params["units"])
        caches["units"] = unit_caches
    if tail_kinds:
        tail_caches = []
        for p_l, kind in zip(params["tail"], tail_kinds):
            x, a, c = _layer_full(p_l, x, cfg, kind, ctx)
            aux_total = aux_total + a
            tail_caches.append(c)
        caches["tail"] = tuple(tail_caches)
    return x, aux_total, caches


def _paged_here(cfg: ModelConfig, kind: str, ctx: Dict) -> bool:
    """True when ``kind``'s cache leaf in this step is a block pool: the
    step carries block tables and the layer is linear attention KV."""
    return (ctx.get("block_tables") is not None
            and paged_layer_kind(cfg, kind))


def _trunk_cached(params: Dict, x: jax.Array, cfg: ModelConfig,
                  cache: Dict, ctx: Dict, layer_fn) -> Tuple[jax.Array, Dict]:
    """Run a cached step (decode, chunked prefill, verify) over the trunk.

    The stacked unit caches ride in the scan's carry, not its xs/ys, so
    the step updates them in place (docs/ARCHITECTURE.md §5): a paged
    pool leaf gets its new rows scattered at ``(layer, block, offset)``
    and its attended view gathered as ``pool[layer, tables]``, with no
    per-layer slice and no stacked output written; a per-slot leaf
    (dense KV, ring buffer, recurrent state) is sliced at the layer and
    written back to the same carried array. Together with the engine
    donating the cache, no step copies the whole pool."""
    n_units, tail_kinds = _split_layers(cfg)
    new_cache: Dict[str, Any] = {}
    if n_units:
        def unit_body(carry, scanned):
            x, unit_cache = carry
            unit_params, layer = scanned
            new_unit_cache = []
            for pos, kind in enumerate(cfg.block_pattern):
                p, c = unit_params[pos], unit_cache[pos]
                if _paged_here(cfg, kind, ctx):
                    x, c = layer_fn(p, x, cfg, kind, c, ctx, layer=layer)
                else:
                    here = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                        a, layer, keepdims=False), c)
                    x, here = layer_fn(p, x, cfg, kind, here, ctx)
                    c = jax.tree.map(
                        lambda a, n: jax.lax.dynamic_update_index_in_dim(
                            a, n, layer, 0), c, here)
                new_unit_cache.append(c)
            return (x, tuple(new_unit_cache)), None

        (x, new_cache["units"]), _ = jax.lax.scan(
            unit_body, (x, cache["units"]),
            (params["units"], jnp.arange(n_units, dtype=jnp.int32)))
    if tail_kinds:
        tail_caches = []
        for p_l, kind, c_l in zip(params["tail"], tail_kinds, cache["tail"]):
            x, c = layer_fn(p_l, x, cfg, kind, c_l, ctx)
            tail_caches.append(c)
        new_cache["tail"] = tuple(tail_caches)
    return x, new_cache


def _encoder_apply(params: Dict, embeds: jax.Array, cfg: ModelConfig,
                   proj: jax.Array) -> jax.Array:
    x = embeds @ proj
    B, T, _ = x.shape
    positions = default_positions(B, T)
    ctx = {"positions": positions, "attn_impl": "auto", "chunk": 512,
           "return_cache": False}

    def body(x, layer_p):
        h = apply_norm(layer_p["attn_norm"], x, cfg.norm)
        q, k, v = attn._project_qkv(layer_p["attn"], h, cfg, positions)
        mask = jnp.ones((B, T, T), bool)  # bidirectional
        out = attn._sdpa(q, k, v, mask, 1.0 / float(cfg.head_dim) ** 0.5)
        x = x + out.reshape(B, T, -1) @ layer_p["attn"]["wo"]
        h = apply_norm(layer_p["ffn_norm"], x, cfg.norm)
        y, _ = _ffn_apply(layer_p["ffn"], h, cfg)
        return x + y, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm)


# =====================================================================
# embedding / positions / loss
# =====================================================================
def _embed_inputs(params: Dict, batch: Dict, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array, Optional[Tuple]]:
    """Returns (x (B,S,d), positions (B,S), mrope_positions or None)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x_tok = apply_embed(params["embed"], tokens)
    if cfg.frontend is not None and not cfg.enc_dec:
        fe = batch["frontend_embeds"] @ params["frontend_proj"]
        x = jnp.concatenate([fe.astype(x_tok.dtype), x_tok], axis=1)
        F = fe.shape[1]
        S = x.shape[1]
        positions = default_positions(B, S)
        mrope = None
        if cfg.rope == "mrope":
            grid = max(1, int(F ** 0.5))
            t_v, h_v, w_v = vision_grid_positions(B, F, grid)
            lin = default_positions(B, S - F, offset=F)
            mk = lambda vis, off: jnp.concatenate([vis, lin], 1)  # noqa: E731
            mrope = (mk(t_v, 0), mk(h_v, 0), mk(w_v, 0))
        return x, positions, mrope
    positions = default_positions(B, tokens.shape[1])
    return x_tok, positions, None


def _lm_logits(params: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(x, head, cfg.tie_embeddings, cfg.logit_softcap)


def _xent_chunked(params: Dict, x: jax.Array, labels: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Cross-entropy without materialising (B,S,V): lax.map over S-chunks."""
    B, S, d = x.shape
    chunk = LOSS_CHUNK if S % LOSS_CHUNK == 0 else S
    n = S // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def one(args):
        xi, li = args
        logits = _lm_logits(params, xi, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    losses = jax.lax.map(one, (xc, lc))
    return jnp.mean(losses)


# =====================================================================
# cache construction
# =====================================================================
def paged_layer_kind(cfg: ModelConfig, kind: str) -> bool:
    """True when ``kind``'s decode cache uses the block-pool layout under
    a paged cache: linear (non-windowed) attention KV only. Recurrent
    states are O(1) per sequence and ring buffers are bounded by their
    window, so both stay per-slot dense."""
    if kind in ("rwkv", "rglru") or cfg.enc_dec:
        return False
    return _window_for(cfg, kind) is None


def _layer_cache_spec(cfg: ModelConfig, kind: str, batch: int,
                      cache_len: int, dtype,
                      paged: Optional[Tuple[int, int]] = None) -> Dict:
    window = _window_for(cfg, kind)
    if kind == "rwkv":
        return rk.rwkv_state_spec(cfg, batch, dtype)
    if kind == "rglru":
        return rg.rglru_state_spec(cfg, batch, dtype)
    if paged is not None and paged_layer_kind(cfg, kind):
        n_blocks, block_size = paged
        return attn.paged_kv_cache_spec(cfg, n_blocks, block_size, dtype)
    clen = min(cache_len, window) if window is not None else cache_len
    c = attn.kv_cache_spec(cfg, batch, clen, dtype)
    if cfg.enc_dec:
        enc_len = ModelSpecs.enc_len(cache_len)
        shp = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
        c["ck"] = jax.ShapeDtypeStruct(shp, dtype)
        c["cv"] = jax.ShapeDtypeStruct(shp, dtype)
    return c


def _stack_spec(specs: List[Any]) -> Any:
    return jax.tree.map(
        lambda *leaves: jax.ShapeDtypeStruct(
            (len(leaves),) + leaves[0].shape, leaves[0].dtype), *specs)


def pad_cache(cfg: ModelConfig, cache: Dict, extra: int) -> Dict:
    """Extend linear (non-windowed) KV caches by ``extra`` slots so a
    prefill cache of S entries can absorb decode writes at S..S+extra-1.
    Ring buffers (windowed layers) and recurrent states are fixed-size and
    pass through untouched. Cross-attention K/V is static.

    Paged caches never come through here: a block pool has no length
    axis to pad — capacity grows by *allocating blocks*
    (``scatter_blocks`` + the engine's ``BlockAllocator``), which is the
    whole point of the layout."""
    n_units, tail_kinds = _split_layers(cfg)

    def pad_layer(kind: str, c: Dict, stacked: bool) -> Dict:
        if kind in ("rwkv", "rglru") or _window_for(cfg, kind) is not None:
            return c
        axis = 2 if stacked else 1  # cache-length axis
        out = dict(c)
        for key in ("k", "v"):
            widths = [(0, 0)] * c[key].ndim
            widths[axis] = (0, extra)
            out[key] = jnp.pad(c[key], widths)
        return out

    new: Dict[str, Any] = {}
    if "units" in cache:
        new["units"] = tuple(
            pad_layer(kind, c, stacked=True)
            for kind, c in zip(cfg.block_pattern, cache["units"]))
    if "tail" in cache:
        new["tail"] = tuple(
            pad_layer(kind, c, stacked=False)
            for kind, c in zip(tail_kinds, cache["tail"]))
    return new


def make_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype,
               abstract: bool = False,
               paged: Optional[Tuple[int, int]] = None) -> Dict:
    """Decode-cache pytree for ``batch`` slots of ``cache_len`` tokens.

    ``paged=(n_blocks, block_size)`` switches linear attention KV layers
    to the block-pool layout ``(n_blocks, block_size, KV, hd)`` shared by
    all slots (docs/ARCHITECTURE.md §5); windowed ring buffers and
    recurrent states keep their per-slot dense layout in both modes.
    """
    if paged is not None and cfg.enc_dec:
        raise NotImplementedError(
            "paged KV caches do not support encoder-decoder models")
    n_units, tail_kinds = _split_layers(cfg)
    cache: Dict[str, Any] = {}
    if n_units:
        cache["units"] = tuple(
            _stack_spec([_layer_cache_spec(cfg, kind, batch, cache_len,
                                           dtype, paged)] * n_units)
            for kind in cfg.block_pattern)
    if tail_kinds:
        cache["tail"] = tuple(
            _layer_cache_spec(cfg, kind, batch, cache_len, dtype, paged)
            for kind in tail_kinds)
    if abstract:
        return cache
    # every decode state starts at zero; made at its stacked shape, so
    # no per-layer copy of the pool exists beside the stacked one
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache)


# =====================================================================
# block-granular cache surgery (paged layout)
# =====================================================================
def gather_blocks(pool: jax.Array, block_ids: jax.Array) -> jax.Array:
    """Pure gather: pool (N, bs, ...) + ids (n,) -> (n*bs, ...) logical
    rows in block-table order."""
    bs = pool.shape[1]
    n = block_ids.shape[0]
    return pool[block_ids].reshape((n * bs,) + pool.shape[2:])


def gather_blocks_stacked(pool: jax.Array, block_ids: jax.Array
                          ) -> jax.Array:
    """:func:`gather_blocks` for scan-stacked unit caches: pool
    (U, N, bs, ...) + ids (n,) -> (U, n*bs, ...) logical rows in
    block-table order — the read-side primitive prefix-cache staging
    fills are built from."""
    bs = pool.shape[2]
    n = block_ids.shape[0]
    g = pool[:, block_ids]
    return g.reshape((pool.shape[0], n * bs) + pool.shape[3:])


def _rows_to_blocks(rows: jax.Array, n: int, bs: int) -> jax.Array:
    """Fold a token axis (third-from-last, length T <= n*bs) into
    (n, bs) blocks, zero-padding the ragged tail of the last block."""
    pad = n * bs - rows.shape[-3]
    if pad < 0:
        raise ValueError(
            f"{rows.shape[-3]} rows exceed {n} blocks of {bs}")
    if pad:
        widths = [(0, 0)] * rows.ndim
        widths[-3] = (0, pad)
        rows = jnp.pad(rows, widths)
    return rows.reshape(rows.shape[:-3] + (n, bs) + rows.shape[-2:])


def scatter_blocks(pool: jax.Array, rows: jax.Array,
                   block_ids: jax.Array) -> jax.Array:
    """Pure scatter: write ``rows`` (T, ...) with T <= n*bs into physical
    blocks ``block_ids`` (n,) of ``pool`` (N, bs, ...), zero-padding the
    ragged tail of the last block. This is the block-granular primitive
    prefill grafting is built from — the paged analogue of the dense
    engines' row scatter."""
    blocks = _rows_to_blocks(rows, block_ids.shape[0], pool.shape[1])
    return pool.at[block_ids].set(blocks)


def scatter_blocks_stacked(pool: jax.Array, rows: jax.Array,
                           block_ids: jax.Array) -> jax.Array:
    """:func:`scatter_blocks` for scan-stacked unit caches: pool
    (U, N, bs, ...), rows (U, T, ...) — the same physical blocks written
    in every unit's pool (direct indexed scatter; a vmap here would
    retrace on every admission)."""
    blocks = _rows_to_blocks(rows, block_ids.shape[0], pool.shape[2])
    return pool.at[:, block_ids].set(blocks)


# =====================================================================
# public model API
# =====================================================================
class ModelSpecs:
    VLM_FRONTEND_TOKENS = 1024
    ENC_RATIO = 4  # seamless: encoder frames = seq // 4

    @staticmethod
    def enc_len(seq_len: int) -> int:
        return max(8, seq_len // ModelSpecs.ENC_RATIO)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    attn_impl: str = "auto"
    chunk: int = 512
    remat: bool = True
    #: cast params to this dtype for the forward pass (mixed precision:
    #: bf16 compute against f32 master weights — §Perf: halves every
    #: activation collective and activation buffer). None = no cast.
    compute_dtype: Any = None

    def _cast(self, params):
        if self.compute_dtype is None:
            return params
        from repro.common.types import cast_tree

        return cast_tree(params, self.compute_dtype)

    # ---- params ------------------------------------------------------
    def init(self, rng, dtype=jnp.float32):
        return init_params(rng, self.cfg, dtype)

    def abstract_params(self, dtype=jnp.float32):
        return abstract_params(self.cfg, dtype)

    def _trunk(self, params, batch, *, return_cache: bool, remat: bool):
        """Embed ``batch`` and run the whole trunk: (x, aux, cache)."""
        cfg = self.cfg
        x, positions, mrope = _embed_inputs(params, batch, cfg)
        ctx = {"positions": positions, "mrope_positions": mrope,
               "attn_impl": self.attn_impl, "chunk": self.chunk,
               "return_cache": return_cache}
        if cfg.enc_dec:
            ctx["enc_out"] = _encoder_apply(params, batch["frontend_embeds"],
                                            cfg, params["frontend_proj"])
        return _trunk_full(params, x, cfg, ctx, remat=remat)

    # ---- forward: train ----------------------------------------------
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        params = self._cast(params)
        x, aux, _ = self._trunk(params, batch, return_cache=False,
                                remat=self.remat)
        if cfg.frontend is not None and not cfg.enc_dec:
            F = batch["frontend_embeds"].shape[1]
            x = x[:, F:, :]
        loss = _xent_chunked(params, x, batch["labels"], cfg)
        return loss + 0.01 * aux

    # ---- forward: prefill ----------------------------------------------
    def prefill(self, params, batch):
        params = self._cast(params)
        x, _, cache = self._trunk(params, batch, return_cache=True,
                                  remat=False)
        logits = _lm_logits(params, x[:, -1:, :], self.cfg)
        return logits, cache

    def logits(self, params, batch) -> jax.Array:
        """Full-sequence forward: (B,S,V) logits at every position, no
        cache — the plain reference a served greedy sequence is checked
        against (teacher-forced over prompt + generated tokens)."""
        params = self._cast(params)
        x, _, _ = self._trunk(params, batch, return_cache=False, remat=False)
        return _lm_logits(params, x, self.cfg)

    # ---- forward: chunked prefill ---------------------------------------
    def prefill_chunk(self, params, cache, batch):
        """Chunked-prefill continuation (docs/ARCHITECTURE.md §5):
        ``batch = {"tokens": (B,T), "pos": (B,)}`` processes T tokens
        starting at absolute position ``pos`` against a DENSE decode
        cache previously filled up to ``pos`` (zeros on first chunk) —
        or, when ``batch["block_tables"]`` is present, directly against
        a PAGED pool: the chunk's K/V is scattered through the table and
        its queries attend earlier blocks in place (the engine's fused
        prefill path, no staging gather/scatter round trip).
        Returns (last-position logits, cache). Attention attends exactly
        the positions a full prefill attends, recurrent layers run their
        sequence form from the carried state — so a prompt processed in
        chunks is math-identical to one processed in a single prefill.
        Frontend/encoder-decoder inputs are not supported (the
        continuous engine gates them to the single-shot prefill path)."""
        cfg = self.cfg
        if cfg.enc_dec or cfg.frontend is not None:
            raise NotImplementedError(
                "prefill_chunk supports plain token prompts only")
        params = self._cast(params)
        x = apply_embed(params["embed"], batch["tokens"])
        ctx = {"pos": batch["pos"], "attn_impl": self.attn_impl,
               "block_tables": batch.get("block_tables")}
        x, new_cache = _trunk_cached(params, x, cfg, cache, ctx,
                                     _layer_chunk)
        logits = _lm_logits(params, x[:, -1:, :], cfg)
        return logits, new_cache

    # ---- forward: speculative verification -------------------------------
    def verify_step(self, params, cache, batch):
        """Speculative-decoding verification forward
        (docs/ARCHITECTURE.md §5): ``batch = {"tokens": (B,W), "pos":
        (B,)}`` plus, for paged caches, ``"block_tables": (B, nb)``
        scores W candidate tokens per sequence in ONE forward — the
        logits at column ``j`` are exactly what sequential
        :meth:`decode_step` of ``tokens[:, j]`` at position ``pos + j``
        would produce — and writes their K/V rows. Returns
        (all-position logits (B,W,V), cache).

        The engine is responsible for masking / rolling back the rows of
        rejected candidates; that is only sound for rewindable caches
        (linear-attention KV), so callers gate on
        ``serving.engine.supports_speculation``. Paged callers must also
        pad ``block_tables`` with null-block columns so rows past
        ``cache_len`` cannot clip into live blocks."""
        cfg = self.cfg
        if cfg.enc_dec or cfg.frontend is not None:
            raise NotImplementedError(
                "verify_step supports plain token prompts only")
        params = self._cast(params)
        x = apply_embed(params["embed"], batch["tokens"])
        ctx = {"pos": batch["pos"], "attn_impl": self.attn_impl,
               "block_tables": batch.get("block_tables")}
        x, new_cache = _trunk_cached(params, x, cfg, cache, ctx,
                                     _layer_chunk)
        logits = _lm_logits(params, x, cfg)
        return logits, new_cache

    # ---- forward: decode -----------------------------------------------
    def decode_step(self, params, cache, batch):
        """batch = {"tokens": (B,1), "pos": (B,)} plus, for paged caches,
        "block_tables": (B, nb) int32; returns (logits, cache)."""
        cfg = self.cfg
        params = self._cast(params)
        x = apply_embed(params["embed"], batch["tokens"])
        ctx = {"pos": batch["pos"], "attn_impl": self.attn_impl,
               "block_tables": batch.get("block_tables")}
        x, new_cache = _trunk_cached(params, x, cfg, cache, ctx,
                                     _layer_decode)
        logits = _lm_logits(params, x, cfg)
        return logits, new_cache

    # ---- caches ---------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, dtype=jnp.float32):
        return make_cache(self.cfg, batch, cache_len, dtype, abstract=False)

    def cache_spec(self, batch: int, cache_len: int, dtype=jnp.float32):
        return make_cache(self.cfg, batch, cache_len, dtype, abstract=True)

    def init_paged_cache(self, batch: int, cache_len: int, n_blocks: int,
                         block_size: int, dtype=jnp.float32):
        """Paged decode cache: linear-attention KV in a shared
        ``(n_blocks, block_size, KV, hd)`` pool, windowed/recurrent state
        per-slot dense at ``batch`` slots (docs/ARCHITECTURE.md §5)."""
        return make_cache(self.cfg, batch, cache_len, dtype,
                          abstract=False, paged=(n_blocks, block_size))

    def paged_cache_spec(self, batch: int, cache_len: int, n_blocks: int,
                         block_size: int, dtype=jnp.float32):
        return make_cache(self.cfg, batch, cache_len, dtype,
                          abstract=True, paged=(n_blocks, block_size))

    # ---- input specs (dry-run stand-ins) ---------------------------------
    def input_specs(self, shape: InputShape, dtype=jnp.float32) -> Dict:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = lambda b, s: jax.ShapeDtypeStruct((b, s), jnp.int32)  # noqa
        if shape.kind == "train":
            specs: Dict[str, Any] = {}
            if cfg.enc_dec:
                specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (B, ModelSpecs.enc_len(S), cfg.d_model), dtype)
                specs["tokens"] = tok(B, S)
                specs["labels"] = tok(B, S)
            elif cfg.frontend is not None:
                F = min(cfg.frontend_tokens or ModelSpecs.VLM_FRONTEND_TOKENS,
                        S // 2)
                specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (B, F, cfg.d_model), dtype)
                specs["tokens"] = tok(B, S - F)
                specs["labels"] = tok(B, S - F)
            else:
                specs["tokens"] = tok(B, S)
                specs["labels"] = tok(B, S)
            return specs
        if shape.kind == "prefill":
            specs = {"tokens": tok(B, S)}
            if cfg.enc_dec:
                specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (B, ModelSpecs.enc_len(S), cfg.d_model), dtype)
            elif cfg.frontend is not None:
                F = min(cfg.frontend_tokens or ModelSpecs.VLM_FRONTEND_TOKENS,
                        S // 2)
                specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (B, F, cfg.d_model), dtype)
                specs["tokens"] = tok(B, S - F)
            return specs
        # decode: one token against a cache of length S
        return {"tokens": tok(B, 1),
                "pos": jax.ShapeDtypeStruct((B,), jnp.int32)}

    def supports_shape(self, shape: InputShape) -> bool:
        cfg = self.cfg
        if shape.name == "long_500k":
            return cfg.subquadratic
        return True


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
