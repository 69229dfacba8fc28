"""GQA attention: full-sequence (train/prefill) and single-token decode.

Three full-sequence implementations, selected by ``impl``:

* ``naive``   — materialises (S, T) scores; fine for short smoke shapes and
                used as the correctness oracle.
* ``chunked`` — lax.map over query chunks; peak memory O(C*T) instead of
                O(S*T). This is the shape the dry-run lowers at 32k so the
                compiled HLO never materialises a quadratic buffer.
* ``kernel``  — Pallas flash-attention (TPU target; interpret-mode on CPU).

Decode attends one new token against a KV cache. Caches are linear
(``cache_len == max_seq``) or ring buffers (``cache_len == window``) for
sliding-window layers; ring entries store keys already rotated at their
absolute positions.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import dense_init, norm_init, apply_norm
from repro.models.rope import apply_rope

NEG_INF = -2.0e38


# ---------------------------------------------------------------- params
def attn_init(rng, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(rng, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype,
                         scale=1.0 / jnp.sqrt(cfg.n_heads * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, "rmsnorm", dtype)
        p["k_norm"] = norm_init(hd, "rmsnorm", dtype)
    if cfg.qkv_bias:
        # keys of their own, so a model without the bias draws as before
        kb = jax.random.split(jax.random.fold_in(rng, 1), 3)
        for key, name, heads in zip(kb, ("bq", "bk", "bv"),
                                    (cfg.n_heads, cfg.n_kv_heads,
                                     cfg.n_kv_heads)):
            p[name] = (jax.random.normal(key, (heads * hd,), jnp.float32)
                       * d ** -0.5).astype(dtype)
    return p


def _project_qkv(p: Dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array,
                 mrope_positions=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    hd = cfg.head_dim

    def project(name: str, heads: int) -> jax.Array:
        y = x @ p["w" + name]
        if cfg.qkv_bias:
            y = y + p["b" + name]
        return y.reshape(B, S, heads, hd)

    q = project("q", cfg.n_heads)
    k = project("k", cfg.n_kv_heads)
    v = project("v", cfg.n_kv_heads)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope, cfg.rope_theta, mrope_positions)
    k = apply_rope(k, positions, cfg.rope, cfg.rope_theta, mrope_positions)
    return q, k, v


def _sdpa(q, k, v, mask, scale) -> jax.Array:
    """q (B,Sq,H,hd), k/v (B,T,KV,hd), mask (B,Sq,T) bool -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array,
                 window: Optional[int]) -> jax.Array:
    """q_pos (B,Sq), k_pos (B,T) -> (B,Sq,T) bool."""
    m = q_pos[:, :, None] >= k_pos[:, None, :]
    if window is not None:
        m &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    return m


# ---------------------------------------------------------------- full seq
def attention_full(p: Dict, x: jax.Array, cfg: ModelConfig,
                   positions: jax.Array, *, window: Optional[int] = None,
                   impl: str = "auto", chunk: int = 512,
                   mrope_positions=None) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions, mrope_positions)
    scale = 1.0 / float(cfg.head_dim) ** 0.5
    if impl == "auto":
        impl = "naive" if S <= 2048 else "chunked"
    if impl == "kernel":
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, k, v, positions=positions,
                                   window=window, scale=scale)
    elif impl == "naive":
        mask = _causal_mask(positions, positions, window)
        out = _sdpa(q, k, v, mask, scale)
    elif impl == "chunked":
        if S % chunk:
            chunk = S  # degenerate fallback for odd smoke shapes
        n = S // chunk
        # §Perf (context parallelism): head counts (40/28/10/56...) do not
        # divide the 16-way model axis, so GSPMD otherwise shards head_dim
        # and psums the FULL (B,KV,G,C,T) scores tensor per chunk (the
        # 960 GiB/step finding on llama4 prefill). Sharding K/V on the
        # SEQUENCE dim makes per-chunk scores local; only the softmax
        # stats and the (B,C,H,hd) output reduce across the model axis.
        from repro.models.shard_hooks import constrain

        bspec = ("pod", "data")
        k = constrain(k, bspec, "model", None, None)
        v = constrain(v, bspec, "model", None, None)
        qc = jnp.moveaxis(q.reshape(B, n, chunk, cfg.n_heads, cfg.head_dim),
                          1, 0)  # (n, B, C, H, hd)
        qc = constrain(qc, None, bspec, None, None, None)
        pc = jnp.moveaxis(positions.reshape(B, n, chunk), 1, 0)

        def one(args):
            qi, pi = args
            mask = _causal_mask(pi, positions, window)
            return _sdpa(qi, k, v, mask, scale)

        out = jax.lax.map(one, (qc, pc))  # (n, B, C, H, hd)
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, cfg.n_heads, cfg.head_dim)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")
    return out.reshape(B, S, -1) @ p["wo"]


# ---------------------------------------------------------------- decode
def kv_cache_spec(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Dict:
    hd = cfg.head_dim
    shp = (batch, cache_len, cfg.n_kv_heads, hd)
    return {"k": jax.ShapeDtypeStruct(shp, dtype),
            "v": jax.ShapeDtypeStruct(shp, dtype)}


def paged_kv_cache_spec(cfg: ModelConfig, n_blocks: int, block_size: int,
                        dtype) -> Dict:
    """Block-pool KV layout (docs/ARCHITECTURE.md §5): one physical pool
    of ``n_blocks`` blocks of ``block_size`` tokens shared by every
    sequence, indirected through per-sequence block tables. Block 0 is
    conventionally the *null block* (sink for inactive batch rows)."""
    hd = cfg.head_dim
    shp = (n_blocks, block_size, cfg.n_kv_heads, hd)
    return {"k": jax.ShapeDtypeStruct(shp, dtype),
            "v": jax.ShapeDtypeStruct(shp, dtype)}


def _write_cache(cache: jax.Array, new: jax.Array, slot: jax.Array) -> jax.Array:
    """cache (B,C,KV,hd), new (B,1,KV,hd), slot (B,) -> updated cache."""

    def row(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))

    return jax.vmap(row)(cache, new, slot)


def _pool_index(layer: Optional[jax.Array], *idx) -> Tuple:
    """Index into a block pool (N,bs,KV,hd), or into layer ``layer`` of
    a scan-stacked one (U,N,bs,KV,hd) — one gather or scatter over the
    stacked array, so no layer slice of it is ever materialised."""
    return idx if layer is None else (layer,) + idx


def _write_paged(pool: jax.Array, new: jax.Array, tables: jax.Array,
                 pos: jax.Array, layer: Optional[jax.Array] = None
                 ) -> jax.Array:
    """pool (N,bs,KV,hd) or, with ``layer``, (U,N,bs,KV,hd); new
    (B,1,KV,hd); tables (B,nb); pos (B,).

    Scatter each sequence's new K/V row into block
    ``tables[b, pos//bs]`` at offset ``pos % bs``. Distinct live
    sequences own distinct blocks, so the only colliding writes are
    inactive rows aimed at the null block — last-write-wins there is
    harmless because null-block contents are never read as valid."""
    bs = pool.shape[-3]
    B = new.shape[0]
    blk = tables[jnp.arange(B), pos // bs]
    return pool.at[_pool_index(layer, blk, pos % bs)].set(new[:, 0])


def _gather_paged(pool: jax.Array, tables: jax.Array,
                  layer: Optional[jax.Array] = None) -> jax.Array:
    """The logical view (B, nb*bs, KV, hd) of each sequence's blocks."""
    B, nb = tables.shape
    g = pool[_pool_index(layer, tables)]
    return g.reshape((B, nb * g.shape[2]) + g.shape[3:])


def attention_decode_paged(p: Dict, x: jax.Array, cache: Dict,
                           tables: jax.Array, pos: jax.Array,
                           cfg: ModelConfig, *, impl: str = "auto",
                           layer: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, Dict]:
    """Paged-counterpart of :func:`attention_decode` for linear
    (non-windowed) layers: the new K/V is scattered through the block
    table and the query attends the gathered logical view. Attended
    positions are exactly ``slots <= pos`` — the same set the dense
    layout attends — so greedy decode is token-identical across
    layouts. With ``layer``, ``cache`` holds the scan-stacked pools and
    only that layer's blocks are written and read, in place."""
    B = x.shape[0]
    nb = tables.shape[1]
    bs = cache["k"].shape[-3]
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None])
    cache = {"k": _write_paged(cache["k"], k_new, tables, pos, layer),
             "v": _write_paged(cache["v"], v_new, tables, pos, layer)}
    scale = 1.0 / float(cfg.head_dim) ** 0.5
    if impl == "kernel":
        from repro.kernels import ops as kops

        k_pool, v_pool = (cache["k"], cache["v"]) if layer is None else \
            (cache["k"][layer], cache["v"][layer])
        out = kops.paged_decode_attention(q, k_pool, v_pool,
                                          tables, pos + 1, scale)
    else:
        k = _gather_paged(cache["k"], tables, layer)
        v = _gather_paged(cache["v"], tables, layer)
        valid = jnp.arange(nb * bs, dtype=jnp.int32)[None, :] <= pos[:, None]
        out = _sdpa(q, k, v, valid[:, None, :], scale)
    return out.reshape(B, 1, -1) @ p["wo"], cache


def _write_paged_chunk(pool: jax.Array, new: jax.Array, tables: jax.Array,
                       pos: jax.Array, layer: Optional[jax.Array] = None
                       ) -> jax.Array:
    """pool as in :func:`_write_paged`; new (B,T,KV,hd); tables (B,nb);
    pos (B,).

    Multi-row counterpart of :func:`_write_paged`: row ``j`` of each
    sequence's chunk lands in block ``tables[b, (pos+j)//bs]`` at offset
    ``(pos+j) % bs``. Table columns past a sequence's allocated blocks
    are the null block, so out-of-range rows (speculative drafts past a
    slot's participation depth, inactive batch rows) collide harmlessly
    there; callers must pad ``tables`` wide enough that ``(pos+T-1)//bs``
    never clips into a LIVE column (JAX clamps out-of-bounds gathers)."""
    bs = pool.shape[-3]
    B, T = new.shape[0], new.shape[1]
    p = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B,T)
    blk = jnp.take_along_axis(tables, p // bs, axis=1).reshape(-1)
    rows = new.reshape((B * T,) + new.shape[2:])
    return pool.at[_pool_index(layer, blk, (p % bs).reshape(-1))].set(rows)


def attention_chunk_paged(p: Dict, x: jax.Array, cache: Dict,
                          tables: jax.Array, pos: jax.Array,
                          cfg: ModelConfig, *, impl: str = "auto",
                          layer: Optional[jax.Array] = None
                          ) -> Tuple[jax.Array, Dict]:
    """Speculative-verification chunk over the paged layout
    (docs/ARCHITECTURE.md §5): score ``T`` candidate tokens ``x`` (B,T,d)
    at positions ``pos..pos+T-1`` in one forward. The chunk's K/V is
    scattered through the block table FIRST, then each query attends the
    gathered logical view under the causal mask ``slot <= pos+j`` —
    exactly the positions sequential decode of token ``j`` would attend,
    so the logits at column ``j`` match :func:`attention_decode_paged`
    token for token. Rows for later-rejected candidates stay in the pool
    as garbage but are never attended before being overwritten (decode
    masks ``slots <= pos``; the engine additionally frees whole rejected
    blocks back to its allocator).

    Also the fused chunked-prefill body: the engine's fused prefill path
    calls this per chunk, so prefix-cache hits and chunk continuations
    attend shared blocks directly through the table with no staging
    gather. ``impl="kernel"`` dispatches to the fused Pallas kernel
    (:func:`repro.kernels.ops.paged_prefill_attention`), which streams
    physical blocks instead of gathering the logical view. ``layer`` as
    in :func:`attention_decode_paged`."""
    B, T, _ = x.shape
    nb = tables.shape[1]
    bs = cache["k"].shape[-3]
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, q_pos)
    cache = {"k": _write_paged_chunk(cache["k"], k_new, tables, pos, layer),
             "v": _write_paged_chunk(cache["v"], v_new, tables, pos, layer)}
    scale = 1.0 / float(cfg.head_dim) ** 0.5
    if impl == "kernel":
        from repro.kernels import ops as kops

        k_pool, v_pool = (cache["k"], cache["v"]) if layer is None else \
            (cache["k"][layer], cache["v"][layer])
        out = kops.paged_prefill_attention(q, k_pool, v_pool,
                                           tables, pos, scale)
    else:
        k = _gather_paged(cache["k"], tables, layer)
        v = _gather_paged(cache["v"], tables, layer)
        mask = jnp.arange(nb * bs, dtype=jnp.int32)[None, None, :] \
            <= q_pos[:, :, None]
        out = _sdpa(q, k, v, mask, scale)
    return out.reshape(B, T, -1) @ p["wo"], cache


def _write_chunk_linear(cache: jax.Array, new: jax.Array,
                        pos: jax.Array) -> jax.Array:
    """cache (B,C,KV,hd), new (B,T,KV,hd), pos (B,) -> rows pos..pos+T-1
    of each sequence overwritten with the chunk's K/V."""

    def row(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))

    return jax.vmap(row)(cache, new, pos)


def _write_chunk_ring(cache: jax.Array, new: jax.Array,
                      pos: jax.Array) -> jax.Array:
    """Ring-buffer chunk write: slot ``(pos+j) % C`` must end up holding
    the LAST position of the chunk that maps to it (T may exceed the
    window, in which case early chunk positions are overwritten — the
    same final state sequential decode writes would leave)."""
    B, C = cache.shape[0], cache.shape[1]
    T = new.shape[1]
    slots = jnp.arange(C, dtype=jnp.int32)[None, :]           # (1, C)
    j0 = (slots - pos[:, None]) % C                           # (B, C)
    j_last = j0 + ((T - 1 - j0) // C) * C                     # largest < T
    written = j0 < T
    j_safe = jnp.clip(j_last, 0, T - 1)
    picked = jnp.take_along_axis(
        new, j_safe[:, :, None, None], axis=1)                # (B, C, KV, hd)
    return jnp.where(written[:, :, None, None], picked, cache)


def attention_prefill_chunk(p: Dict, x: jax.Array, cache: Dict,
                            pos: jax.Array, cfg: ModelConfig, *,
                            window: Optional[int] = None,
                            impl: str = "auto") -> Tuple[jax.Array, Dict]:
    """Chunked-prefill continuation (docs/ARCHITECTURE.md §5): process
    ``T`` new tokens ``x`` (B,T,d) starting at absolute position ``pos``
    (B,) against a dense decode cache previously filled up to ``pos``.

    Each chunk query attends (a) the cache contents earlier chunks wrote
    and (b) the causal prefix of its own chunk — exactly the positions a
    full-sequence prefill attends, so chunking is math-identical to
    :func:`attention_full` per query row. The chunk's K/V is then written
    into the cache (linear: rows pos..pos+T-1; windowed: ring slots
    modulo the capacity) leaving the same state sequential decode writes
    would leave."""
    B, T, _ = x.shape
    C = cache["k"].shape[1]
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, q_pos)
    slots = jnp.arange(C, dtype=jnp.int32)[None, :]
    if window is not None:
        # ring: slot s holds the largest p <= pos-1 with p % C == s
        prev = pos[:, None] - 1
        k_pos_old = prev - ((prev - slots) % C)
        old_valid = k_pos_old >= 0
    else:
        k_pos_old = jnp.broadcast_to(slots, (B, C))
        old_valid = slots < pos[:, None]
    old_mask = old_valid[:, None, :] & _causal_mask(q_pos, k_pos_old, window)
    chunk_mask = _causal_mask(q_pos, q_pos, window)
    k_cat = jnp.concatenate([cache["k"], k_new], axis=1)
    v_cat = jnp.concatenate([cache["v"], v_new], axis=1)
    mask = jnp.concatenate([old_mask, chunk_mask], axis=2)
    scale = 1.0 / float(cfg.head_dim) ** 0.5
    out = _sdpa(q, k_cat, v_cat, mask, scale)
    write = _write_chunk_ring if window is not None else _write_chunk_linear
    cache = {"k": write(cache["k"], k_new, pos),
             "v": write(cache["v"], v_new, pos)}
    return out.reshape(B, T, -1) @ p["wo"], cache


def attention_decode(p: Dict, x: jax.Array, cache: Dict, pos: jax.Array,
                     cfg: ModelConfig, *, window: Optional[int] = None,
                     impl: str = "auto") -> Tuple[jax.Array, Dict]:
    """x (B,1,d); pos (B,) absolute position of the new token."""
    B = x.shape[0]
    C = cache["k"].shape[1]
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None])
    slot = pos % C if window is not None else pos
    cache = {"k": _write_cache(cache["k"], k_new, slot),
             "v": _write_cache(cache["v"], v_new, slot)}
    # absolute position held by each cache slot
    slots = jnp.arange(C, dtype=jnp.int32)[None, :]
    if window is not None:
        # ring buffer: slot s holds the largest p <= pos with p % C == s
        k_pos = pos[:, None] - ((pos[:, None] - slots) % C)
        valid = (k_pos >= 0) & (k_pos > pos[:, None] - window)
    else:
        k_pos = slots
        valid = slots <= pos[:, None]
    scale = 1.0 / float(cfg.head_dim) ** 0.5
    if impl == "kernel":
        from repro.kernels import ops as kops

        out = kops.decode_attention(q, cache["k"], cache["v"], valid, scale)
    else:
        mask = valid[:, None, :]  # (B,1,C)
        out = _sdpa(q, cache["k"], cache["v"], mask, scale)
    return out.reshape(B, 1, -1) @ p["wo"], cache
