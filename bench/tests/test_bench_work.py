"""Needed-work counts against hand counts, and the rule that padding,
inactive rows and the block table's width never enter them."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402
import work  # noqa: E402

REF = spec.load_module(BENCH / "references" / "dense_decoder.py",
                       "bench_reference_test_work")


def _sizes(name):
    return REF.sizes(spec.read_json(BENCH / "configs" / f"{name}.json"))


QWEN = _sizes("qwen3-0.6b")
GLM = _sizes("chatglm3-6b-10l")


def test_weight_hand_counts():
    # qwen3-0.6b: per layer q 1024x2048, k and v 1024x1024 each, o
    # 2048x1024, MLP 3 x 1024x3072, norms 2x1024 + 2x128; the embedding
    # is tied, so a call reads it whole as the output head
    per_layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                 + 3 * 1024 * 3072 + 2 * 1024 + 2 * 128)
    assert per_layer == 15_730_944
    params = 28 * per_layer + 151936 * 1024 + 1024
    assert params == 596_049_920                      # 0.596 B
    assert work.weight_bytes(QWEN, 4) == params * 4
    # chatglm3-6b: 204 M per layer, 533 M in the untied embedding and
    # head; a call reads the head whole and the embedding only as the
    # rows it looks up
    per_layer = (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
                 + 3 * 4096 * 13696 + 2 * 4096)
    assert per_layer == 203_956_224
    embed = 65024 * 4096
    assert work.weight_bytes(GLM, 4) == (10 * per_layer + embed + 4096) * 4
    twelve = dict(GLM, layers=12)
    total = work.weight_bytes(twelve, 4) / 4 + embed
    assert total / 1e9 == pytest.approx(2.98, abs=0.005)
    assert total * 4 / 1e9 == pytest.approx(11.9, abs=0.05)
    assert (work.weight_bytes(GLM, 4) + embed * 4) / 1e9 == pytest.approx(
        10.29, abs=0.01)


def test_decode_hand_count():
    P = work.matmul_params(QWEN)
    kv = 2 * 28 * 8 * 128 * 4            # K and V, every layer, float32
    assert work.kv_bytes_per_token(QWEN, 4) == kv == 229_376
    w = work.decode(QWEN, [300, 20], 4)
    assert w["flops"] == 2 * P * 2 + 4 * 28 * 16 * 128 * 320
    assert w["bytes"] == work.weight_bytes(QWEN, 4) + kv * 320 + 2 * 1024 * 4


def test_inactive_rows_and_table_width_never_enter():
    # a decode call's work is a function of the active rows' contexts
    # only: free slots' dummy rows and the table's width are not inputs
    none = work.decode(GLM, [], 4)
    assert none["flops"] == 0
    assert none["bytes"] == work.weight_bytes(GLM, 4)
    one = work.decode(GLM, [17], 4)
    assert work.decode(GLM, [17, 5], 4)["flops"] - one["flops"] == \
        work.decode(GLM, [5], 4)["flops"] - none["flops"]


@pytest.mark.parametrize("start,rows,first_real", [(0, 64, 40),
                                                   (512, 128, 100),
                                                   (0, 512, 0)])
def test_padding_never_enters_prefill(start, rows, first_real):
    padded = work.prefill(QWEN, start, rows, first_real, 4)
    # the same real tokens without the padding in front of them
    lo = max(start, first_real) - first_real
    plain = work.prefill(QWEN, lo, start + rows - max(start, first_real),
                         0, 4)
    assert padded == plain


def test_prefill_hand_count():
    P = work.matmul_params(QWEN)
    w = work.prefill(QWEN, 0, 64, 40, 4)  # 24 real rows, keys 1..24
    assert w["flops"] == 2 * P * 24 + 4 * 28 * 16 * 128 * (24 * 25 // 2)
    kv = work.kv_bytes_per_token(QWEN, 4)
    assert w["bytes"] == work.weight_bytes(QWEN, 4) + kv * 24 + 24 * 1024 * 4
    # a second piece reads the cache of the real positions before it
    w2 = work.prefill(QWEN, 64, 64, 40, 4)
    assert w2["bytes"] == work.weight_bytes(QWEN, 4) + kv * (24 + 64) \
        + 64 * 1024 * 4
    assert work.prefill(QWEN, 0, 32, 32, 4)["flops"] == 0


def test_bound_is_the_larger_of_compute_and_bandwidth():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    w = work.decode(QWEN, [300] * 16, 4)
    assert work.bound_s(w, peak) == pytest.approx(w["bytes"] / 819e9)
    big = work.prefill(GLM, 0, 512, 0, 4)
    assert work.bound_s(big, peak) == pytest.approx(big["flops"] / 197e12)


def test_tokens_per_step_counts_the_users_own_tokens():
    import readers

    mod = spec.metric_reader("engine.tokens_per_step")
    pad = spec.metric_reader("engine.prefill_padding_share")
    # one iteration: a 64-row piece whose prompt starts at row 40 (24 real
    # tokens) and two decoding rows; a second: four decoding rows; a third
    # ends after the traced span and is not read
    calls = [("prefill", 1.0, 0, 64, 40), ("decode", 1.1, [5, 6]),
             ("step", 1.2), ("decode", 2.0, [7, 8, 9, 10]), ("step", 2.1),
             ("prefill", 3.0, 0, 512, 0), ("step", 9.0)]
    ctx = {"calls": calls, "span": {"t0": 0.5, "t1": 5.0}}
    steps = readers.per_step(ctx)
    assert [s["real"] for s in steps] == [26, 4]
    assert mod.read(ctx) == 15.0
    assert pad.read(ctx) == pytest.approx(100.0 * 40 / 64)
    # a piece of padding alone adds rows but no tokens
    ctx["calls"] = [("prefill", 1.0, 0, 32, 32), ("decode", 1.1, [5]),
                    ("step", 1.2)]
    assert mod.read(ctx) == 1.0 and pad.read(ctx) == 100.0
    assert mod.read({"calls": calls, "span": {}}) is None
