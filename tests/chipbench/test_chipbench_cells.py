"""Cells and metrics as data: what BENCHMARK.json names exists, agrees with
the files beside it, and keeps to the contract's character sets."""

import json
import os
import re

import pytest

from chipbench import cells, peaks
from chipbench.check_line import NAME, UNIT

ROOT = cells.BENCH_ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_and_reports_enough(cell):
    c = cells.load_cell(cell)
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2 and c.per_layer
    assert os.path.exists(c.config_path) and c.config["kind"] in ("serve", "train")
    for name in list(c.end_to_end) + list(c.per_layer):
        assert callable(cells.load_reader(name))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_file_agrees_with_the_benchmark(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    with open(os.path.join(ROOT, "chipbench", "metrics", f"{metric}.json")) as f:
        meta = json.load(f)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert meta[key] == entry[key], key
    # The file names the cells accepted with the metric; cells of later PRs
    # are appended in BENCHMARK.json alone (a file that is there is not edited).
    assert set(meta["workloads"]) <= set(entry["workloads"])
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_names_units_and_lines_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    name_chars = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                assert name_chars.match(os.path.relpath(os.path.join(base, f), ROOT)), f


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9")


@pytest.mark.parametrize("trace,seconds,expected", [
    (0, 40.0, 1800.0),  # no capture: the 90th percentile of all twenty waits
    (1, 40.0, 900.0),   # the capture starts at 36.5 s: the ten waits due by 35 s
    (1, 3.0, 1800.0),   # under ten requests are clear of the capture: all of them
])
def test_ttft_reader_leaves_out_what_was_due_near_the_capture(trace, seconds, expected):
    """The choice is by when a request was due, never by how it fared."""
    from types import SimpleNamespace as NS

    t0 = 1000.0
    dues = [seconds * k / 40.0 for k in list(range(0, 30, 3)) + [35.5 + 0.4 * k for k in range(10)]]
    results = [NS(due=t0 + d, token_times=[t0 + d + (i + 1) / 10.0], error=None) for i, d in enumerate(dues)]
    ctx = {"window": (t0, seconds), "results": results, "args": NS(trace=trace)}
    assert cells.load_reader("ttft_p90_pre_capture_ms")(ctx) == pytest.approx(expected)
