"""The reduction from a profiler trace to numbers (chipbench/trace.py), on
a small trace recorded on the v5e (``chipbench.tools.record_trace``: three
rounds of a 4-step scanned ``jit_block`` and a ``jit_step``, captured for
longer than the work lasted) and on made-up planes for what one chip cannot
show (two devices)."""

import os
from types import SimpleNamespace as NS

import pytest

import chipbench_helpers as helpers
from chipbench import trace

RECORDED = os.path.join(helpers.DATA, "tpu1.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_trace_has_the_lines_the_reduction_reads(recorded):
    plane = next(p for p in recorded.planes if p.name == "/device:TPU:0")
    names = {ln.name for ln in plane.lines}
    assert {trace.OPS_LINE, trace.MODULES_LINE} <= names


def test_busy_is_a_union_of_one_line_not_a_sum_over_lines(recorded):
    got = trace.reduce(recorded)
    dev = trace.device_lines(recorded)[0]
    summed = sum(e - s for s, e, _ in dev["ops"]) + sum(e - s for s, e, _ in dev["modules"])
    assert 0 < got["busy_s"] <= got["window_s"]
    # The while encloses its body on the same line, the modules enclose both.
    assert got["busy_s"] < summed / 1e9 / 2


def test_window_is_what_the_device_events_span_not_the_capture(recorded):
    got = trace.reduce(recorded)
    host = [ev for ev in trace.host_activity(recorded)]
    capture = (max(e for _, e, _ in host) - min(s for s, _, _ in host)) / 1e9
    assert got["window_s"] < capture  # the capture outlasted the work
    assert 0.03 < got["window_s"] < 0.08  # three rounds 20 ms apart


def test_steps_of_a_scanned_program(recorded):
    got = trace.reduce(recorded)
    assert [(n, k) for n, _, k in got["program_events"]] == [("jit_block", 4), ("jit_step", 1)] * 3
    assert all(0 < d < 1e-4 for _, d, _ in got["program_events"])


def test_breakdown_counts_no_time_twice(recorded):
    got = trace.reduce(recorded)
    assert not any(name.startswith("%while") for name, _ in got["device_ops"])
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"] * 1.001
    assert all(len(name) <= 80 for name, _ in got["device_ops"])


def fake(planes):
    def line(name, events):
        return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d) for n, s, d in events])

    return NS(planes=[NS(name=p, lines=[line(n, ev) for n, ev in lines.items()]) for p, lines in planes.items()])


def test_two_devices_mean_busy_and_busiest_breakdown():
    prof = fake({
        "/device:TPU:0": {"XLA Ops": [("%a = x", 0, 400), ("%b = x", 300, 300)], "XLA Modules": [("jit_block(1)", 0, 600)]},
        "/device:TPU:1": {"XLA Ops": [("%a = x", 0, 200), ("%all-reduce.1 = x", 800, 200)], "XLA Modules": []},
        "/host:CPU": {"python3": [("PjitFunction(block)", 0, 1000)]},
    })
    got = trace.reduce(prof)
    assert got["devices"] == 2 and got["busiest"] == "/device:TPU:0"
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx((600 + 400) / 2 * 1e-9)  # union per device, mean over devices
    assert got["busiest_busy_s"] == pytest.approx(600e-9)
    assert got["busy_s"] <= got["window_s"]


def test_no_device_operation_gives_none():
    assert trace.reduce(fake({"/host:CPU": {"python3": [("x", 0, 10)]}})) is None
    assert trace.reduce(fake({"/device:TPU:0": {"XLA Ops": [("%a = x", 5, 0)]}})) is None


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([(0, 10), (5, 15)], 0, 20, 15),       # overlapping
    ([(0, 10), (20, 30)], 5, 25, 10),      # cut to the window
    ([(0, 100), (10, 20)], 0, 100, 100),   # nested
    ([], 0, 10, 0),
])
def test_union_seconds(intervals, lo, hi, want):
    assert trace.union_s(intervals, lo, hi) == pytest.approx(want / 1e9)


def test_gaps_are_the_complement_of_the_union():
    assert trace.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8), (9, 10)]
