"""The trace reduction on a small trace recorded on a TPU v5e (the harness
serving tests/data/small.config.json there, one traced span of 0.4 s),
and its interval arithmetic on made-up events. Reading a recorded trace
needs no chip; nothing here imports a TPU library at module scope."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_and_idle_over_the_window(reduced):
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    # labelled gaps leave out only the shortest ones
    assert sum(s for _, s in reduced["idle_gaps"]) <= idle + 1e-9
    labels = {k for k, _ in reduced["idle_gaps"]}
    assert labels <= set(trace_reduce.SPANS) | {trace_reduce.NO_SPAN}
    assert "engine.step" in labels


def test_time_per_executable(reduced):
    mods = reduced["modules_s"]
    assert trace_reduce.seconds_of(mods, "decode_step") > 0.0
    assert trace_reduce.seconds_of(mods, "prefill_chunk") > 0.0
    # executables run one at a time, inside the window
    assert sum(mods.values()) <= reduced["window_s"]


def test_top_ops_and_spans(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert all("/%" in name for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    spans = reduced["spans"]
    assert spans["engine.step"][0] > 0 and spans["pool.step"][0] > 0


def test_union_gaps_and_self_times():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert trace_reduce._gaps([(0, 3), (5, 8)], -1, 10) == [
        (-1, 0), (3, 5), (8, 10)]
    # a loop op of 10 holding two body ops of 3 and 4 keeps 3 for itself
    st = trace_reduce.self_times([("loop", 0, 10), ("a", 1, 3),
                                  ("b", 5, 4), ("c", 12, 2)])
    assert st == {"loop": 3, "a": 3, "b": 4, "c": 2}


def test_names():
    assert trace_reduce.module_name("jit_decode_step(123)") == \
        "jit_decode_step"
    assert trace_reduce.op_name(
        "%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(f32[8,128] %p)") == \
        "%fusion.3 bf16[8,128]"
