"""The served ChatGLM3 against its plain reference, on the CPU at test
widths (tests/data/small_glm.config.json: 2 layers, d 256, 8 query heads
on 2 KV heads of 32 dims, a real QKV bias, eps 1e-5), with the reference's
seeded weights handed to the program through ``program_tree``.

Both sides compute in float32; the CPU runs the program's products at
full float32 precision too, so they differ only by the order of
summation and the form of the rotary and the softmax. The served logits
must match the reference's within ``TOL`` at every position: through the
full forward pass, a prefill then paged decode through the cache, and a
prompt prefilled in pieces. Each of three faults, the bias left out, the
norms at eps 1e-6, and the rotary columns not permuted, moves the logits
by more than ``FAULT`` times that. A whole run of the harness on this
configuration serves, and its comparison with the reference passes, and
the engine's counters agree with the calls the harness records."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402

DATA = BENCH / "tests" / "data"
CONFIG = spec.read_json(DATA / "small_glm.config.json")
REF = spec.load_module(BENCH / "references" / "chatglm3.py",
                       "bench_reference_test_chatglm3")
SIZES = REF.sizes(CONFIG)
SEED = 2 ** 31 + 15
#: logits agree this closely (absolute; the logits here are of order 1):
#: float32 rounding of a few ops per layer over 2 layers reads about 1e-6
TOL = 2e-5
#: each fault moves some logit by at least this many times TOL (the
#: smallest, eps 1e-6, reads about 1e-2)
FAULT = 100
#: positions served: a prompt of 40, then 12 decoded; 4 slots of blocks
PROMPT, DECODE, BS = 40, 12, 16


def _program(**changes):
    from repro.config import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(CONFIG["registry"]),
                              **CONFIG["overrides"], **changes)
    return cfg, build_model(cfg, remat=False, attn_impl="naive")


@pytest.fixture(scope="module")
def weights():
    return REF.init_weights(SIZES, SEED, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(5)
    return rng.integers(1, SIZES["vocab"], (2, PROMPT + DECODE)).astype(
        np.int32)


@pytest.fixture(scope="module")
def reference(weights, tokens):
    ref = REF.Reference(SIZES)
    return ref.logits(weights, ref.hidden(weights, tokens))


def _paged(model, batch):
    per_slot = -(-(PROMPT + DECODE) // BS)
    cache = model.init_paged_cache(batch, PROMPT + DECODE,
                                   batch * per_slot + 1, BS)
    tables = 1 + np.arange(batch * per_slot, dtype=np.int32).reshape(
        batch, per_slot)
    return cache, jnp.asarray(tables)


def served(model, params, tokens, pieces=(PROMPT,)):
    """Logits per position: the full forward pass (every position), then
    the paged path: the prompt prefilled in ``pieces`` (the last logits of
    each) and the rest decoded one token at a time, teacher-forced."""
    full = np.asarray(model.logits(params, {"tokens": jnp.asarray(tokens)}))
    B = tokens.shape[0]
    cache, tables = _paged(model, B)
    at, start = {}, 0
    for n in pieces:
        batch = {"tokens": jnp.asarray(tokens[:, start:start + n]),
                 "pos": jnp.full((B,), start, jnp.int32),
                 "block_tables": tables}
        logits, cache = model.prefill_chunk(params, cache, batch)
        start += n
        at[start - 1] = np.asarray(logits)[:, 0]
    for t in range(start, tokens.shape[1]):
        batch = {"tokens": jnp.asarray(tokens[:, t:t + 1]),
                 "pos": jnp.full((B,), t, jnp.int32),
                 "block_tables": tables}
        logits, cache = model.decode_step(params, cache, batch)
        at[t] = np.asarray(logits)[:, 0]
    return full, at


def _gap(ref, full, at):
    """Largest difference from the reference, over the full pass and the
    paged positions."""
    paged = max(float(np.abs(v - ref[:, t]).max()) for t, v in at.items())
    return max(float(np.abs(full - ref).max()), paged)


def test_sizes_and_program_config_follow_the_published_keys():
    assert SIZES["rotary_dim"] == 16 and SIZES["qkv_bias"] is True
    cfg, _ = _program()
    want = REF.program_config(SIZES)
    assert {k: getattr(cfg, k) for k in want} == want
    assert want["norm_eps"] == 1e-5 and want["rope"] == "rope2d"
    # the published model as a whole: the preset's own fields
    full = REF.program_config(REF.sizes(spec.read_json(
        BENCH / "configs" / "chatglm3-6b.json")))
    from repro.config import get_config

    glm = dataclasses.replace(get_config("chatglm3-6b"), n_layers=10)
    assert {k: getattr(glm, k) for k in full} == full


def test_needed_work_leaves_out_only_the_bias():
    """``work.py`` reads the same sizes from this reference; the bias
    (46,080 parameters at 10 layers, 0.002% of the bytes) is not
    counted, so a roofline share can only read lower, never above 100%."""
    import work

    s = REF.sizes(spec.read_json(BENCH / "configs" / "chatglm3-6b.json"))
    per_layer = (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
                 + 3 * 4096 * 13696 + 2 * 4096)
    assert work.weight_bytes(s, 4) == (10 * per_layer + 65024 * 4096
                                       + 4096) * 4
    bias = 10 * (32 + 2 * 2) * 128
    assert bias == 46_080
    assert bias * 4 / work.weight_bytes(s, 4) < 2.5e-5


def test_rotary_order_maps_adjacent_pairs_to_halves():
    order = REF.rotary_order(8, 4)
    assert order.tolist() == [0, 2, 1, 3, 4, 5, 6, 7]
    order = REF.rotary_order(128, 64)
    assert order[:3].tolist() == [0, 2, 4] and order[32] == 1
    assert order[63] == 63 and sorted(order.tolist()) == list(range(128))


def test_program_tree_matches_the_programs_layout(weights):
    _, model = _program()
    tree = REF.program_tree(weights, SIZES)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(tree)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
    # the fused weights are drawn again, bit for bit, for the forward pass
    qkv, _ = REF.fused_layer(weights, SIZES, 1)
    hd, H = SIZES["head_dim"], SIZES["heads"]
    order = REF.rotary_order(hd, SIZES["rotary_dim"])
    q = np.asarray(qkv)[:, :H * hd].reshape(-1, H, hd)[..., order]
    wq = np.asarray(tree["units"][0]["attn"]["wq"][1]).reshape(-1, H, hd)
    assert np.array_equal(wq, q)


@pytest.mark.parametrize("pieces", [(PROMPT,), (16, 8, 16)],
                         ids=["one-prefill", "chunked"])
def test_served_logits_match_the_reference(weights, tokens, reference,
                                           pieces):
    _, model = _program()
    params = REF.program_tree(weights, SIZES)
    full, at = served(model, params, tokens, pieces)
    assert set(at) == {*(np.cumsum(pieces) - 1),
                       *range(PROMPT, PROMPT + DECODE)}
    assert _gap(reference, full, at) <= TOL


@pytest.mark.parametrize("fault", ["no_bias", "eps_1e-6",
                                   "identity_rotary_order"])
def test_each_departure_from_the_published_model_fails(
        weights, tokens, reference, monkeypatch, fault):
    if fault == "identity_rotary_order":
        monkeypatch.setattr(REF, "rotary_order",
                            lambda hd, rot: np.arange(hd))
    params = REF.program_tree(weights, SIZES)
    if fault == "no_bias":
        attn = params["units"][0]["attn"]
        for name in ("bq", "bk", "bv"):
            attn[name] = jnp.zeros_like(attn[name])
    _, model = _program(**({"norm_eps": 1e-6} if fault == "eps_1e-6"
                           else {}))
    full, at = served(model, params, tokens)
    assert _gap(reference, full, at) > FAULT * TOL


# ---- the harness on this configuration ----------------------------------
def _cell(mix: str, **load) -> spec.Cell:
    base = {"config": "small_glm", "traffic": mix, "warm_s": 1.0,
            "limits": {"logit_gap_widest": 1e-3, "logit_gap_mean": 1e-5}}
    base.update(load)
    bm = spec.benchmark()
    return spec.Cell(name=f"small_glm.{mix}", chips=1, config=CONFIG,
                     traffic=spec.read_json(DATA / f"small.{mix}.json"),
                     load=base, end_to_end=bm["end_to_end"], per_layer=[])


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """``run.run`` with the compile cache in a temporary directory, put
    back as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    import run
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "jc")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield run
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_run_serves_the_published_model_and_passes(harness):
    line = harness.run(_cell("open", rate_rps=8.0), SEED, 2.0, False,
                       on_chip=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for k in ("logit_gap_widest", "logit_gap_mean"):
        gap = line["checks"][k]
        assert 0.0 <= gap["value"] <= gap["limit"]


def test_engine_counters_match_the_recorded_calls():
    import readers
    import run
    from system import System

    cell = _cell("closed", clients=6, max_rps_per_client=40, warm_s=0.5)
    sysm = System(cell, 3, REF, SIZES, spans=True, log=lambda m: None)
    # the bias is served, one row of 8 heads x 32 per layer
    assert sysm.engine.params["units"][0]["attn"]["bq"].shape == (2, 256)
    counters = ("n_decode_rows", "n_prefill_chunk_tokens",
                "n_prefill_pad_rows")
    before = {k: getattr(sysm.engine, k) for k in counters}
    sysm.start()
    try:
        run.drive(sysm, cell, 3, 1.5)
    finally:
        sysm.stop()
    got = {k: getattr(sysm.engine, k) - before[k] for k in counters}
    steps = readers.per_step({"calls": sysm.calls,
                              "span": {"t0": -math.inf, "t1": math.inf}})
    rows = sum(s["prefill_rows"] for s in steps)
    pad = sum(s["prefill_pad"] for s in steps)
    assert rows > pad > 0
    assert got == {"n_decode_rows": sum(s["real"] for s in steps)
                   - (rows - pad),
                   "n_prefill_chunk_tokens": rows,
                   "n_prefill_pad_rows": pad}
