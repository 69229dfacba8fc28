"""A whole run of the harness on the CPU at test widths, past its look
for a chip: it serves, compares with the reference, and prints a result
line. With the timed path broken underneath it, ``correct`` comes out
false, once for each fault a served cell can have. The float8 control
(one below the stated bfloat16) fails the comparison where the served
path passes. Without a TPU,
``bench/run.py`` exits non-zero and prints no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402

DATA = BENCH / "tests" / "data"
BM = spec.benchmark()
#: between the served path (0 on the CPU) and the float8 control at these
#: widths (tests/data/small.config.json; seeds 11-14: widest 0.18-0.28,
#: mean 0.0066-0.015)
LIMITS = {"logit_gap_widest": 1e-3, "logit_gap_mean": 1e-5}


def small_cell(mix: str = "open", **load) -> spec.Cell:
    base = {"config": "small", "traffic": mix, "warm_s": 1.0,
            "limits": LIMITS}
    # a closed loop of more clients than slots keeps every slot busy
    base.update({"rate_rps": 8.0} if mix == "open"
                else {"clients": 6, "max_rps_per_client": 40})
    base.update(load)
    return spec.Cell(name=f"small.{mix}", chips=1,
                     config=spec.read_json(DATA / "small.config.json"),
                     traffic=spec.read_json(DATA / f"small.{mix}.json"),
                     load=base, end_to_end=BM["end_to_end"],
                     per_layer=BM["per_layer"])


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """``run.run`` with the compile cache in a temporary directory, put
    back as it was afterwards."""
    import jax
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


def _run(run, cell, seed=2 ** 31 + 5, seconds=2.0):
    return run.run(cell, seed, seconds, False, on_chip=False)


def test_a_run_serves_checks_and_reports(harness):
    line = _run(harness, small_cell())
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    import traffic

    cell = small_cell()
    due = traffic.requests(cell.traffic, dict(cell.load, cool_s=0.0), 2.0,
                           10, 1)
    assert line["attempted"] == sum(r.phase == "window" for r in due) > 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in BM["end_to_end"]}
    assert line["device"]["platform"] == "cpu"
    for k, limit in LIMITS.items():
        gap = line["checks"][k]
        assert 0.0 <= gap["value"] <= gap["limit"] == limit
    json.dumps(line)


def test_closed_loop_traced_run_reads_per_layer_metrics(harness):
    line = harness.run(small_cell("closed"), 7, 2.0, True, on_chip=False)
    assert line["correct"] is True
    # the CPU has no device plane, so only the host's metrics read
    assert set(line["metrics"]) == {"scheduler.queue_wait_p95_ms",
                                    "engine.tokens_per_step",
                                    "engine.prefill_padding_share"}
    assert line["metrics"]["engine.tokens_per_step"]["value"] > 1.0


def _token_altered(monkeypatch):
    from repro.serving import engine

    real = engine.sample_tokens
    monkeypatch.setattr(engine, "sample_tokens",
                        lambda logits, *a, **k: real(logits, *a, **k) ^ 1)


def _state_unchanged(monkeypatch):
    from repro.models.transformer import Model

    real = Model.decode_step
    monkeypatch.setattr(Model, "decode_step", lambda self, p, cache, b: (
        real(self, p, cache, b)[0], cache))


def _half_batch_left_out(monkeypatch):
    from repro.models.transformer import Model

    real = Model.decode_step

    def half(self, p, cache, b):
        logits, cache = real(self, p, cache, b)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), cache

    monkeypatch.setattr(Model, "decode_step", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(harness, monkeypatch, fault):
    fault(monkeypatch)
    line = _run(harness, small_cell("closed"))
    assert line["correct"] is False
    assert any(line["checks"][k]["value"] > limit
               for k, limit in LIMITS.items())


def test_the_control_fails_where_the_served_path_passes(harness):
    import check

    cell = small_cell("closed")
    run = harness
    ref = spec.reference_module(cell.config)
    sizes = ref.sizes(cell.config)
    from system import System

    served, control = [], []
    for seed in (11, 12, 13):
        sysm = System(cell, seed, ref, sizes, log=lambda m: None)
        sysm.start()
        try:
            res = run.drive(sysm, cell, seed, 2.0)
        finally:
            sysm.stop()
        items = [{"prompt": res["reqs"][r["idx"]].prompt,
                  "tokens": r["tokens"], "serving": cell.serving}
                 for r in check.sample(res["records"], seed)]
        g = check.gaps(ref, sizes, sysm.weights, items,
                       cell.serving["max_seq"],
                       control=cell.config["precision"]["control"])
        served.append(check.numbers(g, "served"))
        control.append(check.numbers(g, "control"))
    for k, limit in LIMITS.items():
        lower = max(s[k] for s in served)
        upper = min(c[k] for c in control)
        assert lower <= limit < upper
        assert upper >= 3 * max(lower, 1e-6)


def test_run_py_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        BM["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=BENCH.parent, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
