"""The readers of the owner loop's phase counters and of the gap labels
(chipbench/readers/_loop.py and the seven that use it), on a pair of
``/metrics`` scrapes and a capture recorded on the v5e from a tiny replica
(``chipbench.tools.record_loop_trace``, cut with its ``--trim``), and on made-up scrapes for the
arithmetic and for the cases that must read 0.0 and never None."""

import json
import math
import os

import pytest

import chipbench_helpers as helpers
from chipbench import cells, trace
from chipbench.tools import loop_account

with open(os.path.join(helpers.DATA, "loop_scrapes.json")) as f:
    RECORDED = json.load(f)
CAPTURE = os.path.join(helpers.DATA, "loop1.xplane.pb")
with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

COUNTER_METRICS = ["loop_prefill_share", "loop_device_wait.batch", "loop_device_wait.chat", "loop_idle_share",
                   "single_step_share", "graft_ms_per_prefill", "page_clear_ms_per_request"]
GAP_METRICS = ["idle_gap_named.batch", "idle_gap_named.chat"]


def seconds(**phases):
    return {f"tpu_engine_loop_{p}_seconds_total": s for p, s in phases.items()}


def ctx_of(before, after, reduced=None):
    return {"scraped": {"before": before, "after": after, "samples": []}, "trace_reduced": reduced}


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(CAPTURE))


def test_recording_is_from_the_chip_and_small():
    assert RECORDED["platform"] == "tpu"
    assert os.path.getsize(CAPTURE) < 500_000
    profile = trace.load(CAPTURE)
    assert any(p.name.startswith("/device:TPU:") for p in profile.planes)
    assert not any(e.name.startswith("$") for p in profile.planes for ln in p.lines for e in ln.events)


def test_recorded_capture_holds_flat_phase_events_beside_device_operations(reduced):
    profile = trace.load(CAPTURE)
    events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name) for p in profile.planes
                    for ln in p.lines for e in ln.events if e.name.startswith("engine."))
    assert {"engine.prefill.graft", "engine.readback", "engine.dispatch"} <= {n for _, _, n in events}
    assert all(b[0] >= a[1] for a, b in zip(events, events[1:]))
    assert reduced is not None and 0 < reduced["busy_s"] < reduced["window_s"]
    assert any(label.startswith("engine.") for label, _ in reduced["idle_gaps"])


def test_operations_carry_their_scope_in_the_capture():
    """The stable device names (looked at by hand, PERF.md section 7 row 9):
    an operation's metadata names the Flax module or ``jax.named_scope`` it
    came from (``tf_op``), though ``ProfileData`` hands out no metadata stat
    and the reduction still keys operations by HLO instruction name."""
    with open(CAPTURE, "rb") as f:
        raw = f.read()
    for scope in (b"/TransformerLM/layer_0/attn/", b"/mlp/", b"/attn/paged_gather/", b"/closed_call/sample/", b"/derive_tables/"):
        assert scope in raw, scope
    for program in (b"jit_block", b"jit_step", b"jit_run"):
        assert program in raw, program


@pytest.mark.parametrize("metric", COUNTER_METRICS)
def test_counter_reader_on_the_recorded_scrapes(metric):
    value = cells.load_reader(metric)(ctx_of(RECORDED["before"], RECORDED["after"]))
    assert isinstance(value, float) and math.isfinite(value) and value >= 0
    if metric.endswith("_share") or metric.startswith("loop_device_wait"):
        assert value <= 100
    if metric in ("loop_prefill_share", "loop_device_wait.batch", "graft_ms_per_prefill", "page_clear_ms_per_request"):
        assert value > 0  # the recording prefilled, decoded and finished requests


@pytest.mark.parametrize("metric", COUNTER_METRICS)
def test_counter_reader_reads_zero_not_none_where_nothing_happened(metric):
    read = cells.load_reader(metric)
    # A program without the counters (the parent commit), and an idle window.
    assert read(ctx_of({}, {})) == 0.0
    assert read(ctx_of(RECORDED["after"], RECORDED["after"])) == 0.0
    assert read(ctx_of({"tpu_engine_requests_total": 3.0}, {"tpu_engine_requests_total": 9.0})) == 0.0


@pytest.mark.parametrize("metric,after,want", [
    ("loop_prefill_share", seconds(prefill=2.0, readback=4.0, sample=1.0, schedule=1.0, idle=12.0, graft=1.5), 25.0),
    ("loop_device_wait.batch", seconds(prefill=2.0, readback=4.0, sample=1.0, schedule=1.0, idle=12.0), 50.0),
    ("loop_device_wait.chat", seconds(readback=3.0, host_gap=1.0), 75.0),
    ("loop_idle_share", seconds(prefill=2.0, readback=4.0, sample=1.0, schedule=1.0, idle=12.0, frontier=0.5), 60.0),
    ("single_step_share", {"tpu_engine_decode_dispatches_step_total": 3.0, "tpu_engine_decode_dispatches_block_total": 9.0}, 25.0),
    ("graft_ms_per_prefill", {**seconds(graft=0.5, prefill=2.0), "tpu_engine_requests_total": 20.0}, 25.0),
    ("page_clear_ms_per_request", {**seconds(clear_slot=0.1), "tpu_engine_cleared_slots_total": 50.0}, 2.0),
])
def test_counter_reader_arithmetic(metric, after, want):
    before = {k: 1.0 for k in after}  # differences, not totals
    assert cells.load_reader(metric)(ctx_of(before, {k: v + 1.0 for k, v in after.items()})) == pytest.approx(want)


@pytest.mark.parametrize("metric", GAP_METRICS)
def test_gap_reader(metric, reduced):
    read = cells.load_reader(metric)
    assert 0 < read(ctx_of({}, {}, reduced)) <= 100
    gaps = [["engine.prefill.graft", 0.3], ["PjitFunction(block)", 0.1], ["host_idle", 0.1]]
    assert read(ctx_of({}, {}, {"idle_gaps": gaps})) == pytest.approx(60.0)
    assert read(ctx_of({}, {}, {"idle_gaps": [["acquire", 0.2]]})) == 0.0  # the parent's labels
    assert read(ctx_of({}, {}, {"idle_gaps": []})) == 0.0
    assert read(ctx_of({}, {}, None)) is None  # no trace, no traced line


def test_new_metrics_are_no_share_of_a_peak_and_name_their_reader():
    for name in COUNTER_METRICS + GAP_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert "mfu" not in name and not name.endswith("_roofline")
        assert entry["source"] == ("program_span" if name in GAP_METRICS else "program_counter")
        assert all(w.startswith("mistral7b-d16.") for w in entry["workloads"])


def test_loop_account_between_two_stamped_samples():
    a = {**seconds(schedule=1.0, prefill=2.0, idle=5.0, graft=1.0), "tpu_engine_steps_total": 10.0}
    b = {**seconds(schedule=2.0, prefill=5.0, readback=4.0, idle=7.0, graft=3.5), "tpu_engine_steps_total": 110.0}
    got = loop_account.account([(100.0, a), (104.0, {}), (110.5, b)])
    assert got["elapsed_s"] == pytest.approx(10.5) and got["accounted_s"] == pytest.approx(10.0)
    assert got["phases"]["prefill"] == pytest.approx(3.0) and got["phases"]["idle"] == pytest.approx(2.0)
    assert got["sub"]["graft"] == (pytest.approx(2.5), "prefill", pytest.approx(3.0))
    assert got["counts"]["tpu_engine_steps_total"] == 100.0
