"""The reader of ``start_prefill_ms_per_admission``
(chipbench/readers/start_prefill_ms_per_admission.py): host milliseconds
building prefill jobs per admitted request, from two counters of the owner
loop that every program since the phase counters has.  Its arithmetic on
made-up scrapes, the cases that must read 0.0 and never None (a traced line
has to hold every metric of its cell), the recorded scrapes of a tiny
replica on the v5e, and the entry it has in BENCHMARK.json."""


import json
import math
import os

import pytest

import chipbench_helpers as helpers
from chipbench import cells

METRIC = "start_prefill_ms_per_admission"
START = "tpu_engine_loop_start_prefill_seconds_total"
REQUESTS = "tpu_engine_requests_total"
with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(helpers.DATA, "loop_scrapes.json")) as f:
    RECORDED = json.load(f)


def ctx_of(before, after):
    return {"scraped": {"before": before, "after": after, "samples": []}, "trace_reduced": None}


@pytest.mark.parametrize("grew,want", [
    ({START: 6.16, REQUESTS: 186.0}, 33.1183),               # batch's account before the maker (PERF.md section 5, PR 29)
    ({START: 7.75, REQUESTS: 320.0}, 24.2188),               # shortchat's
    ({START: 0.5, REQUESTS: 100.0, "tpu_engine_loop_schedule_seconds_total": 9.0}, 5.0),  # the parent phase is not read
    ({START: 0.5, REQUESTS: 100.0, "tpu_engine_prefill_jobs_total": 50.0}, 5.0),          # per request, not per group
])
def test_arithmetic_is_a_difference_over_the_window(grew, want):
    before = {k: 7.0 for k in grew}  # differences, not totals
    after = {k: v + 7.0 for k, v in grew.items()}
    assert cells.load_reader(METRIC)(ctx_of(before, after)) == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                     # a program without the counters
    ({}, {"tpu_engine_steps_total": 9.0}),                        # ... that stepped
    (RECORDED["after"], RECORDED["after"]),                       # an idle window
    ({START: 1.0}, {START: 3.0}),                                 # seconds and no request to set them against
    ({REQUESTS: 1.0}, {REQUESTS: 9.0}),                           # requests and no phase counter
], ids=["no_counters", "no_loop_counters", "idle", "no_requests", "no_start_prefill"])
def test_reads_zero_and_never_none_where_there_is_nothing(before, after):
    assert cells.load_reader(METRIC)(ctx_of(before, after)) == 0.0


def test_on_the_recorded_scrapes():
    value = cells.load_reader(METRIC)(ctx_of(RECORDED["before"], RECORDED["after"]))
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    requests = RECORDED["after"][REQUESTS] - RECORDED["before"].get(REQUESTS, 0.0)
    assert value == pytest.approx(1e3 * (RECORDED["after"][START] - RECORDED["before"].get(START, 0.0)) / requests)


def test_the_entry_names_the_scheduler_layer_and_the_batch_cell():
    """Only the entry's own fields: where it stands in ``per_layer`` and
    what a later PR appends after it, or to its ``workloads``, is pinned
    nowhere.  ``falcon-h1-34b-d6.shortchat`` runs the same line and is not
    listed by the PR that adds the metric: ``test_chipbench_falcon_h1.py``
    pins that cell's traced line at nine metrics (PERF.md section 7 row 17:
    a ``benchmark`` PR appends the cell)."""
    entries = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["source"] == "program_counter" and entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["moves"] == "out_tokens_per_s"
    assert "mistral7b-d16.batch" in entry["workloads"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] in ("slot_occupancy", "loop_prefill_share")}
    assert layers == {entry["layer"]}  # the scheduler layer's name, letter for letter
    assert "mfu" not in METRIC and not METRIC.endswith("_roofline")
    with open(os.path.join(helpers.REPO, "chipbench", "metrics", f"{METRIC}.json")) as f:
        assert json.load(f) == entry


@pytest.mark.parametrize("cell,reports", [
    ("mistral7b-d16.batch", True),
    ("mistral7b-d16.chat", False),
    ("falcon-h1-34b-d6.shortchat", False),
    ("resnet50-b128.train", False),
])
def test_which_cells_report_it(cell, reports):
    loaded = cells.load_cell(cell)
    assert (METRIC in loaded.per_layer) == reports
    if reports:
        assert "out_tokens_per_s" in loaded.end_to_end and loaded.per_layer[METRIC]["unit"] == "ms"
