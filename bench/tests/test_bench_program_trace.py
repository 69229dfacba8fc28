"""The device's idle time put down to the program's own spans
(``program_trace.py``): on made-up intervals, and on a trace recorded on
a TPU v5e while the harness served tests/data/small.config.json in a
closed loop with the program's spans in place (``program.xplane.pb``,
0.2 s traced). Beside it, the
reduction that the per-layer metrics already read gives, key for key,
what it gave when its first fixture was recorded; and the engine's own
work counters agree with what the harness counts from the calls it
records."""
import json
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import program_trace  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"
PROGRAM_TRACE = DATA / "program.xplane.pb"

T, G, E, P = ("repro.driver.turn", "repro.python.gc", "repro.engine.emit",
              "repro.pool.finish")


@pytest.mark.parametrize("spans, gaps, want", [
    # nested on one thread: the deepest open span takes the idle time
    ([(0, 25, T), (5, 8, E)], [(0, 10)], {T: 7, E: 3}),
    # no span open: the idle time is outside the program
    ([(2, 4, T)], [(0, 10)], {T: 2, None: 8}),
    # a collection inside an engine span takes its part, as the driver's
    ([(0, 25, T), (5, 8, E), (6, 7, G)], [(0, 10)], {T: 7, E: 2, G: 1}),
    # two spans opened together: the shorter is the inner one
    ([(5, 8, E), (5, 6, G)], [(0, 10)], {None: 7, G: 1, E: 2}),
    # busy time between gaps is nobody's; a span over two gaps counts in
    # both
    ([(0, 30, T), (8, 22, P)], [(0, 10), (20, 30)],
     {T: 16, P: 4}),
], ids=["nested", "outside", "gc", "tie", "two-gaps"])
def test_innermost_span_takes_the_idle_time(spans, gaps, want):
    got = program_trace.innermost_idle(gaps, spans)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))


def test_layers_and_outside_sum_to_the_idle_time():
    spans = [(0, 25, T), (5, 8, E), (6, 7, G), (22, 40, P), (12, 13, T),
             (60, 61, T)]
    gaps = [(0, 10), (20, 30), (35, 50)]
    red = program_trace.split(spans, gaps, 0, 50)
    assert red["program_idle_s"] == pytest.approx(
        {"driver": 10e-9, "engine": 2e-9, "pool": 13e-9, "outside": 10e-9})
    assert sum(red["program_idle_s"].values()) == pytest.approx(35e-9)
    assert [n for n, _ in red["program_idle_top"]] == [P, T, E, G]
    # turns start at 0 and 12 inside the window; the one at 60 is not
    assert red["driver_turn_max_s"] == pytest.approx(12e-9)


def test_layer_names():
    assert program_trace.layer_of("repro.scheduler.act") == "scheduler"
    assert program_trace.layer_of("repro.python.gc") == "driver"


def test_the_reduction_reads_as_when_its_fixture_was_recorded():
    got = json.loads(json.dumps(trace_reduce.reduce(DATA / "small.xplane.pb")))
    want = json.loads((DATA / "small.reduced.json").read_text())
    for k, v in want.items():
        assert got[k] == v, k


@pytest.fixture(scope="module")
def program():
    return (program_trace.reduce(PROGRAM_TRACE),
            trace_reduce.reduce(PROGRAM_TRACE))


def test_recorded_program_spans_split_the_idle_time(program):
    red, base = program
    idle = base["window_s"] - base["busy_s"]
    assert red["window_s"] == base["window_s"]
    assert red["busy_s"] == pytest.approx(base["busy_s"], abs=1e-12)
    assert sum(red["program_idle_s"].values()) == pytest.approx(idle,
                                                                abs=1e-9)
    assert {"driver", "pool", "engine"} <= set(red["program_idle_s"])
    assert all(n.startswith("repro.") for n, _ in red["program_idle_top"])
    assert 0 < red["driver_turn_max_s"] < red["window_s"]


def test_engine_counters_match_the_recorded_calls():
    import readers
    import run
    from system import System

    cell = spec.Cell(
        name="small.closed", chips=1,
        config=spec.read_json(DATA / "small.config.json"),
        traffic=spec.read_json(DATA / "small.closed.json"),
        load={"config": "small", "traffic": "closed", "warm_s": 0.5,
              "clients": 6, "max_rps_per_client": 40},
        end_to_end=[], per_layer=[])
    ref = spec.reference_module(cell.config)
    sysm = System(cell, 3, ref, ref.sizes(cell.config), spans=True,
                  log=lambda m: None)
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
