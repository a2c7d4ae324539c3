"""The reader of ``frontier_ms_per_dispatch``
(chipbench/readers/frontier_ms_per_dispatch.py): host milliseconds of the
frontier pass per decode dispatch, from three counters of the owner loop
that every program since the phase counters has.  Its arithmetic on made-up
scrapes, the cases that must read 0.0 and never None (a traced line has to
hold every metric of its cell), the recorded scrapes of a tiny replica on
the v5e, and the entry it has in BENCHMARK.json."""


import json
import math
import os

import pytest

import chipbench_helpers as helpers
from chipbench import cells

METRIC = "frontier_ms_per_dispatch"
FRONTIER = "tpu_engine_loop_frontier_seconds_total"
STEP = "tpu_engine_decode_dispatches_step_total"
BLOCK = "tpu_engine_decode_dispatches_block_total"
with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(helpers.DATA, "loop_scrapes.json")) as f:
    RECORDED = json.load(f)


def ctx_of(before, after):
    return {"scraped": {"before": before, "after": after, "samples": []}, "trace_reduced": None}


@pytest.mark.parametrize("grew,want", [
    ({FRONTIER: 0.9, STEP: 30.0, BLOCK: 60.0}, 10.0),        # both programs count as dispatches
    ({FRONTIER: 8.74, STEP: 0.0, BLOCK: 482.0}, 18.1328),    # the parent's shortchat account (PERF.md section 5)
    ({FRONTIER: 0.05, STEP: 100.0}, 0.5),                    # single steps alone, the block counter absent
    ({FRONTIER: 0.482, BLOCK: 482.0, "tpu_engine_loop_dispatch_seconds_total": 9.0}, 1.0),  # the parent phase is not read
])
def test_arithmetic_is_a_difference_over_the_window(grew, want):
    before = {k: 7.0 for k in grew}  # differences, not totals
    after = {k: v + 7.0 for k, v in grew.items()}
    assert cells.load_reader(METRIC)(ctx_of(before, after)) == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                     # a program without the counters
    ({}, {"tpu_engine_requests_total": 9.0}),                     # ... that served requests
    (RECORDED["after"], RECORDED["after"]),                       # an idle window
    ({FRONTIER: 1.0}, {FRONTIER: 3.0}),                           # seconds and no dispatch to set them against
    ({STEP: 1.0, BLOCK: 1.0}, {STEP: 5.0, BLOCK: 9.0}),           # dispatches and no frontier counter
], ids=["no_counters", "no_loop_counters", "idle", "no_dispatches", "no_frontier"])
def test_reads_zero_and_never_none_where_there_is_nothing(before, after):
    assert cells.load_reader(METRIC)(ctx_of(before, after)) == 0.0


def test_on_the_recorded_scrapes():
    value = cells.load_reader(METRIC)(ctx_of(RECORDED["before"], RECORDED["after"]))
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    dispatches = sum(RECORDED["after"][k] - RECORDED["before"].get(k, 0.0) for k in (STEP, BLOCK))
    assert value == pytest.approx(1e3 * (RECORDED["after"][FRONTIER] - RECORDED["before"].get(FRONTIER, 0.0)) / dispatches)


def test_the_entry_names_the_cache_layer_and_the_batch_cell():
    """Only the entry's own fields: where it stands in ``per_layer`` and
    what a later PR appends after it, or to its ``workloads``, is pinned
    nowhere.  ``falcon-h1-34b-d6.shortchat`` runs the same mechanism and is
    not listed by the PR that adds the metric: ``test_chipbench_falcon_h1.py``
    pins that cell's traced line at nine metrics (PERF.md section 7 row 17:
    a ``benchmark`` PR appends the cell)."""
    entries = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["source"] == "program_counter" and entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["moves"] == "out_tokens_per_s"
    assert "mistral7b-d16.batch" in entry["workloads"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] in ("graft_ms_per_prefill", "page_clear_ms_per_request")}
    assert layers == {entry["layer"]}  # the cache layer's name, letter for letter
    assert "mfu" not in METRIC and not METRIC.endswith("_roofline")


@pytest.mark.parametrize("cell,reports", [
    ("mistral7b-d16.batch", True),
    ("mistral7b-d16.chat", False),
    ("resnet50-b128.train", False),
])
def test_which_cells_report_it(cell, reports):
    loaded = cells.load_cell(cell)
    assert (METRIC in loaded.per_layer) == reports
    if reports:
        assert "out_tokens_per_s" in loaded.end_to_end and loaded.per_layer[METRIC]["unit"] == "ms"
