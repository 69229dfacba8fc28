"""Flash-decoding: one query token against a long KV cache, Pallas TPU.

The cache sweep is the innermost, sequential grid axis; the running
max / denominator / accumulator live in VMEM scratch. Cache blocks
stream HBM->VMEM; the query row and accumulator stay
resident. Invalid cache slots (ring-buffer holes, unwritten tail) are
masked via the ``valid`` operand, which also carries per-row positions so
the same kernel serves linear and ring caches.

Two cache layouts:

* ``decode_attention``        — dense (B, C, KV, hd) per-slot caches, grid
  (batch, q_head, cache_blocks).
* ``paged_decode_attention``  — vLLM-style block pool (N, bs, KV, hd)
  indirected through a per-sequence **block table** (docs/ARCHITECTURE.md
  §5): the grid sweeps (batch, *logical* blocks) and the block table,
  scalar-prefetched so the index map can resolve logical→physical before
  the DMA is issued, picks the physical pool block to stream — all KV
  heads of it at once, so the pool is read in its own layout. The
  ragged tail is masked from per-sequence lengths (the paged
  counterpart of the ``valid`` operand).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, n_blocks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bc, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bc, hd)
    valid = valid_ref[0]                          # (1, bc) bool
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1,bc)
    s = jnp.where(valid, s, NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_c", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, scale: float, *, block_c: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q (B,1,H,hd); k/v (B,C,KV,hd); valid (B,C) bool -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    C, KV = k.shape[1], k.shape[2]
    qpk = H // KV
    bc = min(block_c, C)
    C_pad = -(-C // bc) * bc
    kt = jnp.moveaxis(k, 2, 1)  # (B,KV,C,hd)
    vt = jnp.moveaxis(v, 2, 1)
    val = valid
    if C_pad != C:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, C_pad - C), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, C_pad - C), (0, 0)))
        val = jnp.pad(valid, ((0, 0), (0, C_pad - C)))
    qt = jnp.moveaxis(q, 2, 1)  # (B,H,1,hd)
    val = val[:, None, :]  # (B,1,C)
    n_blocks = C_pad // bc

    kernel = functools.partial(_decode_kernel, scale=scale,
                               n_blocks=n_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bc, hd),
                         lambda b, h, j, _qpk=qpk: (b, h // _qpk, j, 0)),
            pl.BlockSpec((1, 1, bc, hd),
                         lambda b, h, j, _qpk=qpk: (b, h // _qpk, j, 0)),
            pl.BlockSpec((1, 1, bc), lambda b, h, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, val)
    return jnp.moveaxis(out, 1, 2)


def online_softmax_update(q, k, v, mask, scale, m_scr, l_scr, acc_scr):
    """One flash step: fold a (bs, hd) K/V block into the (rows, 1)
    running max / denominator and the (rows, hd) accumulator."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG)                   # (rows, bs)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def softmax_scratch(rows: int, hd: int) -> list:
    """VMEM carry for :func:`online_softmax_update`: ``rows`` query rows
    (2-D throughout, one row per query head and chunk position)."""
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32)]


def pool_block_spec(pool: jax.Array, index_map) -> pl.BlockSpec:
    """One physical block ``(1, bs, KV, hd)`` of an ``(N, bs, KV, hd)``
    pool, all KV heads at once.

    The TPU lowering needs a block's last two dims to be divisible by
    (8, 128) or to equal the array's own. A per-head block
    ``(1, bs, 1, hd)`` fails that, and viewing the pool as
    ``(N, bs, KV*hd)`` is no fix: HBM tiles the last two dims, so that
    view is a relayout copy of the whole pool before every call. Taking
    every KV head keeps the last two dims ``(KV, hd)`` whole: each grid
    step streams one contiguous block and the kernel picks heads out of
    it in VMEM."""
    _, bs, kv, hd = pool.shape
    return pl.BlockSpec((1, bs, kv, hd), index_map)


def _decode_heads(q_ref, k_ref, v_ref, mask, scale, m_scr, l_scr, acc_scr,
                  *, n_kv: int, qpk: int):
    """Fold one (bs, KV, hd) pool block into the carry of every query
    head: query heads ``h*qpk .. (h+1)*qpk-1`` read KV head ``h``."""
    for h in range(n_kv):
        rows = pl.ds(h * qpk, qpk)
        online_softmax_update(q_ref[0, 0, rows, :].astype(jnp.float32),
                              k_ref[0, :, h, :].astype(jnp.float32),
                              v_ref[0, :, h, :].astype(jnp.float32),
                              mask, scale, m_scr.at[rows], l_scr.at[rows],
                              acc_scr.at[rows])


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         block_size: int, n_blocks: int, n_kv: int,
                         qpk: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the serial sweep is bounded by the sequence's live block count,
    # not the table width: blocks past the frontier are skipped entirely
    @pl.when(j * block_size < len_ref[b])
    def _update():
        # ragged tail: logical slot j*bs + i is valid iff < seq_len[b]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1) \
            + j * block_size
        _decode_heads(q_ref, k_ref, v_ref, slot < len_ref[b], scale,
                      m_scr, l_scr, acc_scr, n_kv=n_kv, qpk=qpk)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _dead_to_null(j, tbl, lens, b, bs):
    """Index-map helper: physical block for logical block j of sequence
    b, with blocks past the frontier redirected to the null block so the
    padded tail of the table is never read (its entries may be garbage).
    """
    return jnp.where(j * bs < lens[b], tbl[b, j], 0)


@functools.partial(jax.jit,
                   static_argnames=("scale", "max_blocks", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           seq_lens: jax.Array, scale: float, *,
                           max_blocks: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """Flash-decoding over a paged KV pool.

    q (B,1,H,hd); k_pool/v_pool (N, bs, KV, hd) physical blocks;
    block_tables (B, nb) int32 — logical block j of sequence b lives in
    physical block ``block_tables[b, j]``. Grid (batch, logical_blocks):
    each step streams one whole pool block (:func:`pool_block_spec`)
    and folds it into every query head. Entries past a sequence's
    frontier (``j*bs >= seq_lens[b]``) are **never read**: the index map
    redirects dead columns to the null block and ``pl.when`` skips their
    compute, so the serial sweep is bounded by each sequence's live
    block count rather than the table width. ``max_blocks`` (static)
    additionally trims the grid when the caller knows a tighter bound on
    ``max(ceil(seq_lens / bs))``. seq_lens (B,) int32 — number of valid
    logical slots per sequence. Returns (B,1,H,hd).
    """
    B, _, H, hd = q.shape
    bs, KV = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    if max_blocks is not None:
        nb = max(1, min(nb, max_blocks))

    def kv_map(b, j, tbl, lens, _bs=bs):
        return (_dead_to_null(j, tbl, lens, b, _bs), 0, 0, 0)

    def q_map(b, j, tbl, lens):
        return (b, 0, 0, 0)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               block_size=bs, n_blocks=nb, n_kv=KV,
                               qpk=H // KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, seq_lens
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, H, hd), q_map),
            pool_block_spec(k_pool, kv_map),
            pool_block_spec(v_pool, kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, H, hd), q_map),
        scratch_shapes=softmax_scratch(H, hd),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pool, v_pool)


def _paged_splitk_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, block_size: int,
                         blocks_per_split: int, n_kv: int, qpk: int):
    b = pl.program_id(0)
    s = pl.program_id(1)
    jj = pl.program_id(2)
    j = s * blocks_per_split + jj  # logical block index

    @pl.when(jj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_size < len_ref[b])
    def _update():
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1) \
            + j * block_size
        _decode_heads(q_ref, k_ref, v_ref, slot < len_ref[b], scale,
                      m_scr, l_scr, acc_scr, n_kv=n_kv, qpk=qpk)

    @pl.when(jj == blocks_per_split - 1)
    def _finalize():
        # per-split partials: UNNORMALIZED accumulator plus the split's
        # running max / denominator; the host-side reduction combines
        # them with a stable log-sum-exp
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("scale", "n_splits", "interpret"))
def paged_decode_attention_splitk(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array,
                                  block_tables: jax.Array,
                                  seq_lens: jax.Array, scale: float, *,
                                  n_splits: int = 4,
                                  interpret: bool = False) -> jax.Array:
    """Split-K flash-decoding over a paged KV pool.

    Same contract as :func:`paged_decode_attention`, but the logical KV
    axis is partitioned into ``n_splits`` independent grid slices, each
    producing a partial (max, denominator, unnormalized accumulator)
    triple; a block-wise max/sum reduction pass outside the kernel
    rescales and merges them. On hardware the splits run in parallel, so
    long-context decode latency drops from O(blocks) to
    O(blocks / n_splits + n_splits). Dead blocks (past each sequence's
    frontier) are skipped and their table entries never read; a split
    whose every block is dead contributes weight exp(NEG - m) = 0.
    """
    B, _, H, hd = q.shape
    bs, KV = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    n_splits = max(1, min(n_splits, nb))
    bps = -(-nb // n_splits)          # blocks per split
    n_splits = -(-nb // bps)          # drop splits that would be empty

    def kv_map(b, s, jj, tbl, lens, _bps=bps, _bs=bs, _nb=nb):
        # clamp j for the (padded) final split before the table read;
        # dead blocks (incl. all j >= nb, whose slots are >= lens) are
        # redirected to the null block and skipped in-kernel
        j = s * _bps + jj
        phys = jnp.where(j * _bs < lens[b], tbl[b, jnp.minimum(j, _nb - 1)],
                         0)
        return (phys, 0, 0, 0)

    def part_map(b, s, jj, tbl, lens):
        return (b, s, 0, 0)

    kernel = functools.partial(_paged_splitk_kernel, scale=scale,
                               block_size=bs, blocks_per_split=bps,
                               n_kv=KV, qpk=H // KV)
    # every partial's block spans its last two dims whole: (H, hd)
    # accumulators and (H, 1) statistics per (sequence, split)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, seq_lens
        grid=(B, n_splits, bps),
        in_specs=[
            pl.BlockSpec((1, 1, H, hd),
                         lambda b, s, jj, tbl, lens: (b, 0, 0, 0)),
            pool_block_spec(k_pool, kv_map),
            pool_block_spec(v_pool, kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, hd), part_map),
            pl.BlockSpec((1, 1, H, 1), part_map),
            pl.BlockSpec((1, 1, H, 1), part_map),
        ],
        scratch_shapes=softmax_scratch(H, hd),
    )
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_splits, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pool, v_pool)

    # reduction pass: rescale each split's partials to the global max
    m_g = jnp.max(m_p, axis=1, keepdims=True)      # (B,1,H,1)
    w = jnp.exp(m_p - m_g)                         # empty split -> 0
    l_g = jnp.maximum(jnp.sum(l_p * w, axis=1), 1e-30)       # (B,H,1)
    o = jnp.sum(o_p * w, axis=1) / l_g                       # (B,H,hd)
    return o[:, None].astype(q.dtype)
