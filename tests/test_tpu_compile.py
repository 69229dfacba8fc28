"""The main path compiles for a TPU v5e chip, with no chip attached.

Ahead-of-time compiles against a described ``v5e:2x2`` topology: the
TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, too much fast memory, a program that does not fit the
device). Covered at the widths of qwen3-0.6b (16 query / 8 KV heads,
head_dim 128, block size 16), in float32 and bfloat16:

* the three paged Pallas kernels and ``flash_attention``; the paged
  kernels must read the pool in place (no relayout copy of it, which
  would cost a whole-pool HBM round trip per call);
* the jitted paged ``decode_step`` of full-width qwen3-0.6b, from
  ``jax.eval_shape`` shapes;
* the engine's donated ``decode_step`` and ``prefill_chunk`` at the
  chat cell's shapes, which must update the pool in place: the whole
  pool aliased to the output, no pool-sized temporary, and no copy or
  slice of the pool or of one layer of it; the decode step's flat form
  must also gather only its live blocks, not the tables' full width;
* the same two steps of ChatGLM3-6B cut to 10 layers, the benchmark's
  largest model, which must fit one chip with both pools aliased;
* both steps of both models with the weights stored in bfloat16 under
  the float32 pool, as the engine serves them on the chip: no
  instruction outside a fusion writes a weight-shaped array through a
  convert or in a type other than bfloat16.

The topology is described inside a module fixture, never at import: a
test worker that loads the TPU library holds it until it exits, so only
the worker given this file may do so. The persistent compilation cache
is off around the compiles (an entry written for a described chip cannot
be read back without one).
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_attention_splitk)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.prefill_attention import paged_prefill_attention

# a pool the size one chip would serve from (about 134 MB in float32),
# too big for the compiler to stage it whole in fast memory
B, H, KV, HD, BS, N_BLOCKS, NB, T = 4, 16, 8, 128, 16, 2049, 8, 16
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, dtype, sh):
    pool = _sds((N_BLOCKS, BS, KV, HD), dtype, sh)
    tables = _sds((B, NB), jnp.int32, sh)
    lens = _sds((B,), jnp.int32, sh)
    if name == "paged_decode":
        return (lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n, SCALE),
            (_sds((B, 1, H, HD), dtype, sh), pool, pool, tables, lens))
    if name == "paged_decode_splitk":
        return (lambda q, k, v, t, n: paged_decode_attention_splitk(
            q, k, v, t, n, SCALE),
            (_sds((B, 1, H, HD), dtype, sh), pool, pool, tables, lens))
    if name.startswith("paged_prefill"):
        t = T if name == "paged_prefill" else 200  # two query tiles
        return (lambda q, k, v, t, p: paged_prefill_attention(
            q, k, v, t, p, SCALE),
            (_sds((B, t, H, HD), dtype, sh), pool, pool, tables, lens))
    seq = _sds((1, 256, KV, HD), dtype, sh)
    return (lambda q, k, v: flash_attention(q, k, v, scale=SCALE),
            (_sds((1, 256, H, HD), dtype, sh), seq, seq))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["paged_decode", "paged_decode_splitk",
                                  "paged_prefill", "paged_prefill_tiles",
                                  "flash"])
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, args = _kernel_case(name, dtype, one_chip)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    # no op rewrites the pool on its way into the kernel
    relayout = re.compile(
        rf"= \S+\[{N_BLOCKS},[^=]* (copy|reshape|transpose|fusion)\(")
    assert not [line for line in hlo.splitlines() if relayout.search(line)]


def test_qwen3_paged_decode_step_compiles_for_v5e(one_chip):
    """Full-width qwen3-0.6b decode step over the paged pool, at the
    shapes the one-chip server uses (4 slots, 128-token cache)."""
    from repro.config import get_config
    from repro.models import build_model

    cfg = get_config("qwen3-0.6b")
    model = build_model(cfg, remat=False)
    slots, max_seq, bs = 4, 128, 16
    per_slot = max_seq // bs

    def place(tree):
        return jax.tree.map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = place(model.abstract_params(jnp.float32))
    cache = place(model.paged_cache_spec(slots, max_seq,
                                         slots * per_slot + 1, bs,
                                         jnp.float32))
    batch = {"tokens": _sds((slots, 1), jnp.int32, one_chip),
             "pos": _sds((slots,), jnp.int32, one_chip),
             "block_tables": _sds((slots, per_slot), jnp.int32, one_chip)}
    compiled = jax.jit(model.decode_step).lower(params, cache,
                                                batch).compile()
    mem = compiled.memory_analysis()
    # 0.6 B float32 parameters: ~2.4 GB, inside one chip's 16 GB
    assert 2.0e9 < mem.argument_size_in_bytes < 16e9


#: the chat cell's serving shape: slots, positions per slot, block size
CHAT_SLOTS, CHAT_SEQ, CHAT_BS = 16, 768, 16


def _chat_step(one_chip, cfg, step, weight_dtype=jnp.float32, flat=0):
    """``step`` of ``cfg`` at the chat cell's shapes (16 slots, 768
    positions, 769 blocks of 16, a float32 pool and activations, weights
    in ``weight_dtype``), jitted as the engine jits it
    (``jit_cache_step``, the cache donated) and compiled for the chip;
    with ``flat``, the decode step's flat form over that many live
    blocks. Returns the executable, the bytes of one stacked pool and
    the block count."""
    from repro.models import build_model
    from repro.serving.engine import jit_cache_step

    model = build_model(cfg, remat=False, activation_dtype=jnp.float32)
    slots, bs = CHAT_SLOTS, CHAT_BS
    per_slot = CHAT_SEQ // bs
    n_blocks = slots * per_slot + 1

    def place(tree):
        return jax.tree.map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = place(model.abstract_params(weight_dtype))
    cache = place(model.paged_cache_spec(slots, CHAT_SEQ, n_blocks, bs,
                                         jnp.float32))
    rows, width = (slots, 1) if step == "decode_step" else (1, 128)
    batch = {"tokens": _sds((rows, width), jnp.int32, one_chip),
             "pos": _sds((rows,), jnp.int32, one_chip),
             "block_tables": _sds((rows, per_slot), jnp.int32, one_chip)}
    if flat:
        batch["live_blocks"] = _sds((3, flat), jnp.int32, one_chip)
    compiled = jit_cache_step(getattr(model, step)).lower(
        params, cache, batch).compile()
    pool_bytes = cfg.n_layers * n_blocks * bs * cfg.n_kv_heads \
        * cfg.head_dim * 4
    return compiled, pool_bytes, n_blocks


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_qwen3_paged_step_updates_pool_in_place(one_chip, step):
    """Full-width qwen3-0.6b at the chat cell's shapes, jitted as the
    engine jits it: the carried, donated pool is written in place, so
    the executable aliases both whole pools and holds no temporary as
    large as one."""
    from repro.config import get_config

    cfg = get_config("qwen3-0.6b")
    compiled, pool_bytes, n_blocks = _chat_step(one_chip, cfg, step)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    # the rest of the temporary is the per-layer gathered table view,
    # activations and logits; a pool-sized one means a copy came back
    assert mem.temp_size_in_bytes < pool_bytes
    units, bs = cfg.n_layers, CHAT_BS
    shape = rf"f32\[(?:{units},|1,)?{n_blocks},{bs},{cfg.n_kv_heads}," \
        rf"{cfg.head_dim}\]"
    moved = re.compile(
        rf"%\S*(?:copy|dynamic-slice|dynamic-update-slice)\S* = {shape}"
        rf"|= {shape}\S* (?:copy|dynamic-slice|dynamic-update-slice)\(")
    hlo = compiled.as_text()
    assert not [line for line in hlo.splitlines() if moved.search(line)]


def test_qwen3_flat_decode_reads_only_live_blocks(one_chip):
    """The decode step's flat form, at the chat cell's shapes with the
    weights in bfloat16 as served and 128 live blocks: both pools still
    aliased in place, no temporary as large as one, and the KV gathered
    at the live width, never at the tables' full width (16 slots x 48
    blocks)."""
    from repro.config import get_config

    cfg = get_config("qwen3-0.6b")
    compiled, pool_bytes, _ = _chat_step(one_chip, cfg, "decode_step",
                                         jnp.bfloat16, flat=128)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes
    kv = rf"{CHAT_BS},{cfg.n_kv_heads},{cfg.head_dim}\]"
    hlo = compiled.as_text()
    assert re.search(rf"\[128,{kv}", hlo)
    table = CHAT_SLOTS * CHAT_SEQ // CHAT_BS
    assert not re.search(rf"\[{table},{kv}", hlo)


#: one v5e chip's HBM as the runtime offers it to a program
V5E_HBM_BYTES = 15.75 * 2 ** 30


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_chatglm3_paged_step_fits_one_chip(one_chip, step):
    """ChatGLM3-6B as published (QKV bias, eps 1e-5) cut to the
    benchmark's 10 layers, at the chat cell's shapes: its 10.3 GB of
    float32 weights, the pool and the step's temporaries fit one chip,
    and the donated pools are written in place. This guards the depth
    cut."""
    from repro.config import get_config

    cfg = dataclasses.replace(get_config("chatglm3-6b"), n_layers=10)
    assert cfg.qkv_bias and cfg.norm_eps == 1e-5
    compiled, pool_bytes, _ = _chat_step(one_chip, cfg, step)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert 10.0e9 < mem.argument_size_in_bytes < 10.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= V5E_HBM_BYTES


def _chatglm3_cut():
    from repro.config import get_config

    return dataclasses.replace(get_config("chatglm3-6b"), n_layers=10)


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_chatglm3_bf16_paged_step_fits_one_chip(one_chip, step):
    """The same cut with its weights stored in bfloat16, as the engine
    serves them on the chip: 5.2 GB of weights beside the float32 pool,
    both pools still aliased in place."""
    compiled, pool_bytes, _ = _chat_step(one_chip, _chatglm3_cut(), step,
                                         jnp.bfloat16)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert 5.0e9 < mem.argument_size_in_bytes < 5.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= V5E_HBM_BYTES


def _unfused_lines(hlo):
    """The executable's lines outside fusion bodies: what it writes to
    memory (a convert inside a fusion reads its operand as stored)."""
    bodies = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    inside = False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            inside = head.group(1) in bodies
        elif not inside:
            yield line


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
@pytest.mark.parametrize("name", ["qwen3-0.6b", "chatglm3-6b"])
def test_bf16_weights_are_never_widened(one_chip, name, step):
    """With bfloat16 weights no step converts a weight: no array of a
    matrix weight's shape, stacked over the layers or one layer of it,
    nor of the embedding or head, is written by a convert or in another
    type than the stored bfloat16."""
    from repro.config import get_config
    from repro.models import build_model

    cfg = _chatglm3_cut() if name == "chatglm3-6b" else get_config(name)
    compiled, _, _ = _chat_step(one_chip, cfg, step, jnp.bfloat16)
    tree = build_model(cfg).abstract_params(jnp.bfloat16)
    shapes = set()
    for leaf in jax.tree.leaves(tree):
        if leaf.ndim == 3:                      # stacked over the layers
            shapes |= {leaf.shape, leaf.shape[1:]}
        elif leaf.ndim == 2 and cfg.vocab_size in leaf.shape:
            shapes.add(leaf.shape)              # embedding, head
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) in shapes
    dims = "|".join(",".join(map(str, s)) for s in sorted(shapes))
    written = re.compile(rf"= (\w+)\[(?:1,)?(?:{dims})\]")
    bad = []
    for line in _unfused_lines(compiled.as_text()):
        m = written.search(line)
        if m and "parameter(" not in line \
                and (m.group(1) != "bf16" or "convert" in line):
            bad.append(line.strip()[:160])
    assert not bad
