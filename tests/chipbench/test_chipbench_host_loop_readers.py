"""The readers of the owner loop's named host seconds
(chipbench/readers/first_token_ms_per_request.py,
state_upload_ms_per_request.py, enqueue_ms_per_dispatch.py,
loop_host_cpu_share.py): the first token's sync, the step state's
re-uploads, the decode enqueue and the loop thread's CPU share, each a
difference of counters over the window.  Their arithmetic on made-up
scrapes, the cases that must read 0.0 and never None (a traced line has to
hold every metric of its cell, and a parent commit has none of the new
counters), and the entries they have in BENCHMARK.json."""

import json
import math
import os

import pytest

import chipbench_helpers as helpers
from chipbench import cells

METRICS = ["first_token_ms_per_request", "state_upload_ms_per_request", "enqueue_ms_per_dispatch",
           "loop_host_cpu_share"]
REQUESTS = "tpu_engine_requests_total"
STEPS = "tpu_engine_decode_dispatches_step_total"
BLOCKS = "tpu_engine_decode_dispatches_block_total"
HOST_CPU = "tpu_engine_loop_host_cpu_seconds_total"
with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(helpers.DATA, "loop_scrapes.json")) as f:
    RECORDED = json.load(f)


def seconds(**phases):
    return {f"tpu_engine_loop_{p}_seconds_total": s for p, s in phases.items()}


def ctx_of(before, after):
    return {"scraped": {"before": before, "after": after, "samples": []}, "trace_reduced": None}


def read(metric, grew):
    before = {k: 5.0 for k in grew}  # differences, not totals
    return cells.load_reader(metric)(ctx_of(before, {k: v + 5.0 for k, v in grew.items()}))


@pytest.mark.parametrize("metric,grew,want", [
    ("first_token_ms_per_request", {**seconds(first_token=2.6, prefill=7.0), REQUESTS: 200.0}, 13.0),
    ("first_token_ms_per_request", {**seconds(first_token=0.0, prefill=7.0), REQUESTS: 200.0}, 0.0),
    ("state_upload_ms_per_request", {**seconds(state=0.8, realign=0.2, dispatch=9.0), REQUESTS: 250.0}, 4.0),
    ("state_upload_ms_per_request", {**seconds(state=0.5), REQUESTS: 100.0}, 5.0),  # no realign counted
    ("enqueue_ms_per_dispatch", {**seconds(enqueue=0.9, dispatch=4.0), STEPS: 300.0, BLOCKS: 300.0}, 1.5),
    ("enqueue_ms_per_dispatch", {**seconds(enqueue=0.2), BLOCKS: 100.0}, 2.0),
    # 40 s of steps less readback 28, spec_verify 0 and first token 2: 10 s
    # of the host's own work, of which the loop computed 6.
    ("loop_host_cpu_share", {**seconds(schedule=2.0, prefill=5.0, dispatch=4.0, readback=28.0, sample=1.0,
                                       first_token=2.0, state=1.0, idle=9.0), HOST_CPU: 6.0}, 60.0),
    ("loop_host_cpu_share", {**seconds(dispatch=2.0, spec_verify=3.0), HOST_CPU: 2.0}, 100.0),
], ids=["first_token", "first_token_none", "state_and_realign", "state_alone", "enqueue", "enqueue_blocks",
        "cpu_share", "cpu_share_without_spec_verify"])
def test_arithmetic_is_a_difference_over_the_window(metric, grew, want):
    assert read(metric, grew) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("before,after", [
    ({}, {}),                                                    # a program without the counters
    ({}, {"tpu_engine_steps_total": 9.0}),                       # ... that stepped
    (RECORDED["before"], RECORDED["after"]),                     # the parent's counters, and none of the new
    (RECORDED["after"], RECORDED["after"]),                      # an idle window
], ids=["no_counters", "no_loop_counters", "parent_program", "idle"])
def test_reads_zero_and_never_none_where_there_is_nothing(metric, before, after):
    value = cells.load_reader(metric)(ctx_of(before, after))
    assert isinstance(value, float) and math.isfinite(value) and value == 0.0


@pytest.mark.parametrize("metric,grew", [
    ("first_token_ms_per_request", seconds(first_token=1.0)),
    ("state_upload_ms_per_request", seconds(state=1.0, realign=1.0)),
    ("enqueue_ms_per_dispatch", seconds(enqueue=1.0)),
    ("loop_host_cpu_share", {HOST_CPU: 1.0}),
], ids=["first_token", "state_upload", "enqueue", "cpu_share"])
def test_seconds_with_nothing_to_set_them_against_read_zero(metric, grew):
    assert read(metric, grew) == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_the_entry_names_the_scheduler_layer_and_the_batch_cell(metric):
    """Only the entry's own fields.  The cells of LongCat-Flash and
    Falcon-H1 run the same loop and are not listed: their traced lines are
    pinned at a count of metrics by the benchmark's own tests."""
    entries = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["source"] == "program_counter" and entry["moves"] == "out_tokens_per_s"
    assert (entry["unit"], entry["better"]) == (("%", "higher") if metric.endswith("_share") else ("ms", "lower"))
    assert entry["workloads"] == ["mistral7b-d16.batch"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] in ("slot_occupancy", "start_prefill_ms_per_admission")}
    assert layers == {entry["layer"]}  # the scheduler layer's name, letter for letter
    assert "mfu" not in metric and not metric.endswith("_roofline")
    with open(os.path.join(helpers.REPO, "chipbench", "metrics", f"{metric}.json")) as f:
        assert json.load(f) == entry
