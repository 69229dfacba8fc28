"""Fused chunk-prefill attention over a paged KV pool, Pallas TPU.

The chunked-prefill path used to gather every logical block of a
sequence into a dense per-slot staging cache before running attention
(docs/ARCHITECTURE.md §5). This kernel removes that round trip: chunk
queries attend *directly* through the block table, streaming physical
pool blocks HBM->VMEM exactly like :mod:`repro.kernels.decode_attention`
but with a whole query chunk resident instead of one row.

Grid (batch, query tile, logical_blocks) with the KV sweep innermost
and sequential; each step streams one whole pool block (all KV heads)
into every query head, and the online-softmax carry (running max /
denominator / accumulator, one row per head and chunk query) lives in
VMEM scratch. The block
table and per-sequence lengths are scalar-prefetched so the index map
resolves logical→physical before the DMA is issued. Three masks happen
in-kernel:

* **causal chunk suffix** — query row i sits at absolute position
  ``pos[b] + i`` and may only see logical slots ``<= pos[b] + i``;
* **ragged tail** — slots past ``pos[b] + T`` within the last live
  block are excluded by the same comparison;
* **dead blocks** — logical blocks entirely past the sequence frontier
  are skipped via ``pl.when`` *and* their table entries are never read:
  the index map redirects them to the null block, so padded table
  columns may hold arbitrary garbage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (NEG, online_softmax_update,
                                           pool_block_spec)

#: chunk rows per query tile: bounds the VMEM the query block, output
#: block and softmax carry take, whatever the chunk length
Q_TILE = 128


def _paged_prefill_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, scale: float,
                          block_size: int, n_blocks: int, chunk: int,
                          q_tile: int, n_kv: int, qpk: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # blocks entirely past the sequence frontier contribute nothing:
    # skip the matmul and leave the carry untouched
    @pl.when(j * block_size < len_ref[b])
    def _update():
        # query row r of tile i is at absolute position
        # pos[b] + i*q_tile + r = len[b] - chunk + i*q_tile + r and
        # attends logical slots <= its own position (this single
        # comparison is both the causal mask and the ragged tail mask)
        slot = jax.lax.broadcasted_iota(jnp.int32, (q_tile, block_size), 1) \
            + j * block_size
        qpos = jax.lax.broadcasted_iota(jnp.int32, (q_tile, block_size), 0) \
            + (len_ref[b] - chunk + i * q_tile)
        mask = slot <= qpos
        # query heads h*qpk .. (h+1)*qpk-1 read KV head h of the block
        for h in range(n_kv):
            k = k_ref[0, :, h, :].astype(jnp.float32)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            for hq in range(h * qpk, (h + 1) * qpk):
                online_softmax_update(q_ref[0, hq].astype(jnp.float32), k,
                                      v, mask, scale, m_scr.at[hq],
                                      l_scr.at[hq], acc_scr.at[hq])

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            pos: jax.Array, scale: float, *,
                            interpret: bool = False) -> jax.Array:
    """Chunk-query attention directly over a paged KV pool.

    q (B,T,H,hd) — a chunk of T query rows per sequence, row i at
    absolute position ``pos[b] + i``; k_pool/v_pool (N, bs, KV, hd)
    physical blocks, with the chunk's own K/V already written through
    the table; block_tables (B, nb) int32 — entries for blocks past the
    chunk frontier are never read (they may hold arbitrary values);
    pos (B,) int32 chunk start positions. Returns (B,T,H,hd) where row i
    attended logical slots ``0..pos[b]+i``. Grid (batch, query tile,
    logical block): each step streams one whole pool block
    (``pool_block_spec``) into every query head of the tile.
    """
    B, T, H, hd = q.shape
    bs, KV = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    q_tile = min(Q_TILE, -(-T // 8) * 8)  # sublane-aligned
    t_pad = -(-T // q_tile) * q_tile
    qt = jnp.moveaxis(q, 2, 1)  # (B,H,T,hd)
    if t_pad != T:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    lens = pos.astype(jnp.int32) + T  # live logical slots per sequence

    def kv_map(b, i, j, tbl, lens, _bs=bs):
        return (jnp.where(j * _bs < lens[b], tbl[b, j], 0), 0, 0, 0)

    def q_map(b, i, j, tbl, lens):
        return (b, 0, i, 0)

    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               block_size=bs, n_blocks=nb, chunk=T,
                               q_tile=q_tile, n_kv=KV, qpk=H // KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lens
        grid=(B, t_pad // q_tile, nb),
        in_specs=[
            pl.BlockSpec((1, H, q_tile, hd), q_map),
            pool_block_spec(k_pool, kv_map),
            pool_block_spec(v_pool, kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, q_tile, hd), q_map),
        scratch_shapes=[pltpu.VMEM((H, q_tile, 1), jnp.float32),
                        pltpu.VMEM((H, q_tile, 1), jnp.float32),
                        pltpu.VMEM((H, q_tile, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, t_pad, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens, qt, k_pool, v_pool)
    return jnp.moveaxis(out[:, :, :T], 1, 2)
