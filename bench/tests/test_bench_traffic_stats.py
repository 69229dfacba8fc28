"""The traffic generator and the tail arithmetic."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

CHAT = spec.read_json(BENCH / "traffic" / "chat.json")
DOCQA = spec.read_json(BENCH / "traffic" / "docqa.json")
OPEN = {"rate_rps": 8.0, "warm_s": 4.0, "cool_s": 60.0}
CLOSED = {"clients": 24, "max_rps_per_client": 2, "warm_s": 4.0}
BIG_SEED = 2 ** 31 + 12345


def _key(reqs):
    return [(r.idx, r.phase, r.due_s, r.prompt.tobytes(), r.max_new)
            for r in reqs]


@pytest.mark.parametrize("mix,load", [(CHAT, OPEN), (DOCQA, CLOSED)],
                         ids=["open", "closed"])
def test_a_seed_yields_the_same_schedule_and_prompts(mix, load):
    a = traffic.requests(mix, load, 20.0, 151936, BIG_SEED)
    b = traffic.requests(mix, load, 20.0, 151936, BIG_SEED)
    c = traffic.requests(mix, load, 20.0, 151936, BIG_SEED + 1)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    for r in a:
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 1
        lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
        assert lo <= len(r.prompt) <= hi
        assert mix["output_len"]["min"] <= r.max_new \
            <= mix["output_len"]["max"]


def test_every_seed_puts_the_same_work_into_the_window():
    def window(seed):
        w = [r for r in traffic.requests(CHAT, OPEN, 30.0, 1000, seed)
             if r.phase == "window"]
        return [(r.due_s, len(r.prompt), r.max_new) for r in w], \
            [r.prompt.tobytes() for r in w]

    (a, tok_a), (b, tok_b) = window(1), window(BIG_SEED)
    # one trace for every seed: the same sizes at the same times; the seed
    # draws only the token ids
    assert a == b and tok_a != tok_b
    assert 200 <= len(a) <= 280                      # rate x window = 240


def test_open_loop_arrivals_are_poisson_and_sizes_independent():
    mix = dict(CHAT, trace_seed=5)
    reqs = traffic.requests(mix, dict(OPEN, warm_s=0.0, cool_s=0.0), 2000.0,
                            1000, 1)
    due = np.asarray([r.due_s for r in reqs])
    gaps = np.diff(due)
    # exponential gaps: mean 1 / rate and a coefficient of variation of
    # 1 (a smoothed or evenly dealt stream reads well under it)
    assert gaps.mean() == pytest.approx(1 / 8.0, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # counts per second spread as a Poisson count does (variance = mean)
    counts = np.histogram(due, bins=np.arange(0.0, 2001.0))[0]
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.15)
    # each size is drawn alone: consecutive prompts are uncorrelated, and
    # the lengths follow the mix's lognormal
    p = np.asarray([len(r.prompt) for r in reqs], float)
    assert abs(np.corrcoef(p[:-1], p[1:])[0, 1]) < 0.05
    assert np.median(p) == pytest.approx(CHAT["prompt_len"]["median"],
                                         rel=0.1)


def test_open_loop_phases_and_due_times():
    reqs = traffic.requests(CHAT, OPEN, 10.0, 1000, 3)
    win = [r for r in reqs if r.phase == "window"]
    assert all(0.0 <= r.due_s < 10.0 for r in win)
    assert all(r.due_s < 0 for r in reqs if r.phase == "warm")
    assert all(r.due_s >= 10.0 for r in reqs if r.phase == "cool")
    assert [r.idx for r in reqs] == list(range(len(reqs)))


def test_closed_loop_prefixes_hold_the_same_sizes():
    a = traffic.closed_loop(DOCQA, 96, 1000, 1)
    b = traffic.closed_loop(DOCQA, 96, 1000, 99)
    assert [(len(r.prompt), r.max_new) for r in a] \
        == [(len(r.prompt), r.max_new) for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in b]


def _rec(idx, phase, due, status="finished", ttft=0.1, n=10, tpot=0.02,
         prompt=100, win_tokens=None):
    first = due + ttft
    return {"idx": idx, "phase": phase, "due": due, "sent": due,
            "status": status, "t_prefill": due + 0.01 if status == "finished"
            else None,
            "t_first": first if status == "finished" else None,
            "t_finish": first + tpot * (n - 1) if status == "finished"
            else None,
            "n_tokens": n if status == "finished" else 0,
            "n_win_tokens": n if win_tokens is None else win_tokens,
            "prompt_len": prompt, "max_new": n,
            "prompt_in_window": status == "finished", "truncated": False,
            "protocol": None, "tokens": [1] * n}


MIX = {"ttft_limit_ms": 1000, "tpot_limit_ms": 100}


def test_tail_arithmetic_counts_failures():
    good = [_rec(i, "window", float(i) / 10) for i in range(19)]
    base = stats.end_to_end(good, (0.0, 2.0), MIX, 60.0)
    assert base["ttft_p95_ms"] == pytest.approx(100.0)
    assert base["slo_attainment"] == 100.0
    # one request that never finished: it misses both limits, is failed,
    # and enters the tail at what it had waited when the run gave up
    bad = good + [_rec(19, "window", 1.9, status="unfinished")]
    out = stats.end_to_end(bad, (0.0, 2.0), MIX, 60.0)
    assert out["info"]["attempted"] == 20 and out["info"]["failed"] == 1
    assert out["slo_attainment"] == pytest.approx(95.0)
    assert out["ttft_p95_ms"] > base["ttft_p95_ms"]
    assert out["tpot_p95_ms"] > base["tpot_p95_ms"]
    # a refused request fails the same way
    refused = good + [_rec(19, "window", 1.9, status="rejected")]
    assert stats.end_to_end(refused, (0.0, 2.0), MIX, 60.0)[
        "ttft_p95_ms"] == out["ttft_p95_ms"]


def test_tails_and_attainment_against_limits():
    recs = [_rec(i, "window", 0.01 * i, ttft=1.5 if i == 0 else 0.1)
            for i in range(10)]
    recs.append(_rec(10, "window", 0.2, tpot=0.2))
    out = stats.end_to_end(recs, (0.0, 1.0), MIX, 60.0)
    assert out["slo_attainment"] == pytest.approx(100.0 * 9 / 11)
    # warm-up and cool-down requests are not attempted, but their tokens
    # delivered inside the window count towards throughput
    recs.append(_rec(11, "warm", -1.0, prompt=50, win_tokens=4))
    out2 = stats.end_to_end(recs, (0.0, 1.0), MIX, 60.0)
    assert out2["info"]["attempted"] == 11
    assert out2["throughput_tok_s"] == pytest.approx(
        out["throughput_tok_s"] + 54)


def test_wrong_answers_and_lost_requests_are_reported():
    recs = [_rec(0, "window", 0.0), _rec(1, "window", 0.1,
                                         status="unfinished")]
    recs[0]["protocol"] = "token index 3, expected 2"
    assert stats.protocol_faults(recs) == [
        "request 0: token index 3, expected 2"]
    assert stats.never_came(recs) == [1]
