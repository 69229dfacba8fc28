"""Chunked prefill (docs/ARCHITECTURE.md §5): model-level chunk
continuation is math-identical to single-shot prefill, the engine's
per-iteration token budget bounds prefill+decode work, and the submit
clamp is surfaced as ``ContinuousResult.truncated``."""
import numpy as np
import pytest

from conftest import KIND_CFGS, TINY
from repro.serving.engine import (ContinuousBatchingEngine, InferenceEngine,
                                  SEQ_BUCKETS, _bucket)


# ------------------------------------------------- model-level identity
@pytest.mark.slow
@pytest.mark.parametrize("kind", sorted(KIND_CFGS))
def test_prefill_chunk_matches_full_prefill(kind):
    """Processing a prompt in chunks through ``prefill_chunk`` must be
    token-identical to one full ``prefill`` — for every layer family
    (linear attention, sliding-window rings, recurrent state, unrolled
    tails)."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.models.transformer import pad_cache

    cfg = KIND_CFGS[kind]
    S, extra = 32, 6
    m = build_model(cfg, remat=False)
    p = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(1, cfg.vocab_size, S).astype(np.int32)
    dec = jax.jit(m.decode_step)

    logits, cache = jax.jit(m.prefill)(p, {"tokens": jnp.asarray(toks[None])})
    cache = pad_cache(cfg, cache, extra)
    ref = [int(jnp.argmax(logits[0, -1]))]
    pos = jnp.array([S], jnp.int32)
    for _ in range(extra - 1):
        lg, cache = dec(p, cache, {"tokens": jnp.asarray([[ref[-1]]],
                                                         jnp.int32),
                                   "pos": pos})
        ref.append(int(jnp.argmax(lg[0, -1])))
        pos = pos + 1

    cache2 = m.init_cache(1, S + extra)
    pc = jax.jit(m.prefill_chunk)
    off = 0
    for c in (8, 8, 16):  # includes a ragged mix of chunk sizes
        lg2, cache2 = pc(p, cache2,
                         {"tokens": jnp.asarray(toks[None, off:off + c]),
                          "pos": jnp.array([off], jnp.int32)})
        off += c
    out = [int(jnp.argmax(lg2[0, -1]))]
    pos = jnp.array([S], jnp.int32)
    for _ in range(extra - 1):
        lg2, cache2 = dec(p, cache2, {"tokens": jnp.asarray([[out[-1]]],
                                                            jnp.int32),
                                      "pos": pos})
        out.append(int(jnp.argmax(lg2[0, -1])))
        pos = pos + 1
    assert ref == out


# ------------------------------------------------- engine semantics
@pytest.mark.slow
def test_budgeted_engine_matches_round_engine_greedy():
    """A tight token budget changes WHEN prefill work happens, never the
    result: greedy outputs stay identical to the round engine."""
    round_eng = InferenceEngine(TINY, max_seq=64)
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64,
                                   token_budget=8)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in (4, 9, 13)]
    ref = [round_eng.generate([p], max_new_tokens=4).tokens[0]
           for p in prompts]
    res = eng.run(prompts, max_new_tokens=4)
    for r, expected in zip(res, ref):
        assert np.array_equal(r.tokens, expected)


@pytest.mark.slow
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_paged_chunked_identity_fused(prefix_cache):
    """The fused path (chunks attend the block pool directly through
    their table row) stays token-identical to the round engine on
    chunked prefill, with and without prefix reuse. The staging cache
    survives only for hybrid stacks, which fused prefill cannot serve
    (covered by the engine fuzz harness)."""
    round_eng = InferenceEngine(TINY, max_seq=64)
    eng = ContinuousBatchingEngine(
        TINY, max_slots=2, max_seq=64, block_size=8,
        token_budget=12, prefix_cache=prefix_cache)
    assert eng.fused_prefill
    rng = np.random.default_rng(6)
    shared = rng.integers(1, 97, 17).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, 97, n).astype(np.int32)])
               for n in (3, 11)]
    prompts += [rng.integers(1, 97, 5).astype(np.int32), prompts[0].copy()]
    ref = [round_eng.generate([p], max_new_tokens=5).tokens[0]
           for p in prompts]
    res = eng.run(prompts, max_new_tokens=5)
    for r, expected in zip(res, ref):
        assert np.array_equal(r.tokens, expected)
    if prefix_cache:
        assert eng.n_prefix_hits >= 1


@pytest.mark.slow
def test_token_budget_bounds_iteration_work():
    """Every step processes at most budget tokens of prefill + the
    resident decodes; a long prompt therefore spans several iterations
    while a resident sequence keeps decoding (no prefill stall)."""
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                   token_budget=16)
    rng = np.random.default_rng(3)
    eng.submit(rng.integers(1, 97, 5).astype(np.int32), max_new_tokens=12)
    for _ in range(3):  # short sequence is resident and decoding
        eng.step()
    long_prompt = rng.integers(1, 97, 60).astype(np.int32)  # bucket 64
    eng.submit(long_prompt, max_new_tokens=2)
    interleaved = 0
    prefill_steps = 0
    done = []
    for _ in range(40):
        decoding_before = len(eng.decoding_slots)
        done.extend(eng.step())
        # budget caps chunk tokens + decodes counted at step start; a
        # prefill completing mid-step adds at most its own decode row
        assert eng.last_step_tokens <= 16 + eng.n_slots
        if eng.prefilling_slots:
            prefill_steps += 1
            if decoding_before:
                interleaved += 1
        if len(done) == 2:
            break
    assert len(done) == 2
    # 64-token bucket at <=15 spare tokens/step: several chunked steps,
    # and the resident decode advanced during them
    assert prefill_steps >= 2
    assert interleaved >= 1
    by_id = {r.request_id: r for r in done}
    assert len(by_id[0].tokens) == 12 and len(by_id[1].tokens) == 2


@pytest.mark.slow
def test_chunk_shapes_stay_bounded():
    """Chunk pieces are powers of two: the prefill-chunk compile cache
    is bounded by piece sizes, not raw prompt lengths."""
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                   token_budget=32)
    rng = np.random.default_rng(4)
    lengths = (3, 9, 15, 17, 30, 33, 50, 60)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in lengths]
    res = eng.run(prompts, max_new_tokens=2)
    assert len(res) == len(lengths)
    sizes = {t for t, _ in eng.prefill_shapes}
    assert all(s & (s - 1) == 0 for s in sizes)  # powers of two
    # bounded by piece sizes <= budget, not by raw prompt lengths
    assert len(sizes) <= 6 and max(sizes) <= 32


# ------------------------------------------------- truncation satellite
@pytest.mark.slow
def test_submit_clamp_is_surfaced_as_truncated():
    """Regression: submit() silently clamped max_new_tokens to the cache
    room — callers got fewer tokens than requested with no signal. The
    clamp is now recorded and surfaced on the result."""
    eng = ContinuousBatchingEngine(TINY, max_slots=1, max_seq=32)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 97, 10).astype(np.int32)  # bucket 16, room 16
    eng.submit(prompt, max_new_tokens=100)
    eng.submit(rng.integers(1, 97, 10).astype(np.int32), max_new_tokens=4)
    res = sorted(eng.run([], max_new_tokens=0),
                 key=lambda r: r.request_id)
    clamped, ok = res[0], res[1]
    assert clamped.truncated and len(clamped.tokens) == 16
    assert not ok.truncated and len(ok.tokens) == 4
