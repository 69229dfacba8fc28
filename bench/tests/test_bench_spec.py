"""BENCHMARK.json and the files it names: every cell resolves, every name
and unit is of the allowed characters, every metric has its reader, and
every configuration matches the program's registered config."""
import dataclasses
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402

BM = spec.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
LINE_RE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BM) == TOP_KEYS
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    assert (BENCH.parent / BM["command"][1]).is_file()
    assert len(json.dumps(BM)) < 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BM["configs"]] + CELLS \
        + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert spec.NAME_RE.match(n), n
    for w in BM["workloads"]:
        assert spec.NAME_RE.match(w["config"]) and spec.NAME_RE.match(
            w["traffic"])
        assert LINE_RE.match(w["why"]) and w["chips"] in (1, 4)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BM["configs"]:
        assert LINE_RE.match(c["source"]) and LINE_RE.match(c["why"])
        assert all(spec.NAME_RE.match(k) for k in c["reduced"])


def test_metric_entries():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and LINE_RE.match(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = spec.resolve(cell, BM)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.per_layer:
        reader = spec.metric_reader(m["name"])
        assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
        assert callable(reader.read)
    ref = spec.reference_module(c.config)
    sizes = ref.sizes(c.config)
    from system import buckets_used

    # the longest prompt's bucket and the longest reply fit one slot
    assert max(buckets_used(c.serving, c.traffic["prompt_len"])) \
        + c.traffic["output_len"]["max"] <= c.serving["max_seq"]
    assert c.traffic["loop"] in ("open", "closed")
    import check

    assert set(check.NUMBERS) <= set(c.load["limits"])
    import numpy as np

    prec = c.config["precision"]
    assert np.dtype(prec["control"]).itemsize \
        < np.dtype(prec["stated"]).itemsize \
        <= np.dtype(prec["storage"]).itemsize
    assert sizes["vocab"] > 1


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda e: e["name"])
def test_config_file_matches_program_and_entry(entry):
    from repro.config import get_config

    config = spec.read_json(BENCH.parent / entry["file"])
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert entry["file"].startswith("bench/")
    widths = re.compile(r"(_dim|_rank|hidden|intermediate|ffn|head_size"
                        r"|experts_per_tok|kv_channels)")
    assert not any(widths.search(k) for k in entry["reduced"])
    ref = spec.reference_module(config)
    want = ref.program_config(ref.sizes(config))
    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config.get("overrides", {}))
    assert {k: getattr(cfg, k) for k in want} == want


def test_peaks_table_is_keyed_by_device_kind():
    peaks = spec.read_json(BENCH / "peaks.json")
    row = peaks["devices"]["TPU v5 lite"]
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert peaks["source"]


def test_every_metric_file_is_named_in_benchmark_json():
    named = {m["name"] for m in BM["per_layer"]}
    files = {p.name[:-3] for p in (BENCH / "metrics").glob("*.py")}
    assert files == named
