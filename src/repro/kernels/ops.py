"""jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend the kernels run in ``interpret=True`` mode, which
executes the kernel body with the same blocking/masking logic — that is
what the per-kernel allclose tests validate; on any other backend they
lower natively (tests/test_tpu_compile.py compiles them for v5e).
``ref.py`` holds the pure-jnp oracles.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref  # noqa: F401  (re-exported for tests)
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import (
    paged_decode_attention as _paged_decode)
from repro.kernels.decode_attention import (
    paged_decode_attention_splitk as _paged_decode_splitk)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.prefill_attention import (
    paged_prefill_attention as _paged_prefill)
from repro.kernels.moe_matmul import moe_matmul as _moe
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, positions=None, window: Optional[int] = None,
                    scale: float, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """positions accepted for API parity with the model layer; the kernel
    assumes contiguous 0..S-1 prefill positions (asserted by the caller)."""
    del positions
    return _flash(q, k, v, scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k,
                  interpret=_interpret())


def decode_attention(q, k, v, valid, scale: float, block_c: int = 512):
    return _decode(q, k, v, valid, scale, block_c=block_c,
                   interpret=_interpret())


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens,
                           scale: float, max_blocks: Optional[int] = None):
    return _paged_decode(q, k_pool, v_pool, block_tables, seq_lens, scale,
                         max_blocks=max_blocks, interpret=_interpret())


def paged_decode_attention_splitk(q, k_pool, v_pool, block_tables,
                                  seq_lens, scale: float,
                                  n_splits: int = 4):
    return _paged_decode_splitk(q, k_pool, v_pool, block_tables, seq_lens,
                                scale, n_splits=n_splits,
                                interpret=_interpret())


def paged_prefill_attention(q, k_pool, v_pool, block_tables, pos,
                            scale: float):
    return _paged_prefill(q, k_pool, v_pool, block_tables, pos, scale,
                          interpret=_interpret())


def rwkv6_scan(r, k, v, w, u, state, chunk: int = 64):
    return _rwkv(r, k, v, w, u, state, chunk=chunk, interpret=_interpret())


def rglru_scan(a, x, h0, chunk: int = 128, block_w: int = 512):
    return _rglru(a, x, h0, chunk=chunk, block_w=block_w,
                  interpret=_interpret())


def moe_matmul(x, w, **kw):
    return _moe(x, w, interpret=_interpret(), **kw)
