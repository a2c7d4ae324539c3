"""The owner loop's phases (models/engine_profiler.py): one construct,
three sinks.  A tiny EngineServer under traffic; ``POST /debug/trace``;
the capture read with the benchmark's own trace reader; ``/metrics`` and
``GET /debug/profile`` beside it."""

from __future__ import annotations

import dataclasses
import json
import shutil
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as bench_trace
from chipbench.run import parse_exposition
from k8s_device_plugin_tpu.models import engine_profiler
from k8s_device_plugin_tpu.models.engine import PagedConfig, ServingEngine
from k8s_device_plugin_tpu.models.engine_profiler import (
    ALL_PHASES,
    IDLE,
    PHASES,
    SUB_PHASES,
    EngineProfiler,
)
from k8s_device_plugin_tpu.models.engine_types import EngineMetrics
from k8s_device_plugin_tpu.models.http_server import EngineServer
from k8s_device_plugin_tpu.models.transformer import GPTConfig, TransformerLM
from k8s_device_plugin_tpu.utils.metrics import MetricsRegistry
from k8s_device_plugin_tpu.utils.spans import ENGINE_TRACE, SpanRecorder

LOOP = PHASES + (IDLE,)
PROMPT_LENS = (4, 8, 16)


def _counter(phase: str) -> str:
    short = "prefill_chunk" if phase == "prefill.chunk" else phase.split(".")[-1]
    return f"tpu_engine_loop_{short}_seconds_total"


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return resp.read().decode()


def _engine_events(profile):
    """(start_ns, end_ns, name, line) of every ``engine.*`` host event."""
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, f"{plane.name}/{ln.name}")
        for plane in profile.planes for ln in plane.lines for e in ln.events
        if e.name.startswith("engine.")
    )


def _all_names(profile):
    return {e.name for plane in profile.planes for ln in plane.lines for e in ln.events}


@pytest.fixture(scope="module")
def captured():
    """One server, warmed; two clients in closed loops with a pause (so the
    loop also goes idle) while ``/debug/trace`` captures 1 s; scrapes of
    ``/metrics`` on the same clock around the traffic.  Every request
    finishes inside a decode block of 4 (12 tokens), so each finish
    re-aligns the device lengths (``finish.realign``), directly or by
    discarding the overlapped block."""
    cfg = dataclasses.replace(GPTConfig.tiny(), max_seq=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    registry = MetricsRegistry()
    engine = ServingEngine(
        cfg, params, PagedConfig(page_size=4, num_pages=64, max_pages_per_seq=16),
        max_slots=3, metrics=EngineMetrics(registry), decode_block=4,
        spans=SpanRecorder(),
    )
    server = EngineServer(
        engine, host="127.0.0.1", port=0, registry=registry, enable_trace=True,
    ).start()
    port = server.port
    try:
        for n in PROMPT_LENS:  # every program the traffic needs, compiled
            _post(port, "/generate", {"prompt": list(range(1, n + 1)), "max_new_tokens": 12})
        halt = threading.Event()

        def client(i):
            k = 0
            while not halt.is_set():
                n = PROMPT_LENS[k % len(PROMPT_LENS)]
                _post(port, "/generate", {"prompt": list(range(1 + i, n + 1 + i)), "max_new_tokens": 12})
                k += 1
                time.sleep(0.03)

        clients = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(2)]
        t0, before = time.perf_counter(), parse_exposition(_get(port, "/metrics"))
        for c in clients:
            c.start()
        reply = _post(port, "/debug/trace", {"seconds": 1.0})
        halt.set()
        for c in clients:
            c.join(timeout=120)
        # The loop is idle now: no phase but a 0.1 s idle wait is open.
        after, t1 = parse_exposition(_get(port, "/metrics")), time.perf_counter()
        frames = _post(port, "/debug/trace", {"seconds": 0.2, "python_frames": True})
        out = {
            "profile": bench_trace.load(bench_trace.find_xplane(reply["trace_dir"])),
            "frames_profile": bench_trace.load(bench_trace.find_xplane(frames["trace_dir"])),
            "before": before, "after": after, "elapsed": t1 - t0,
            "debug_profile": json.loads(_get(port, "/debug/profile")),
            "spans": engine.spans.snapshot(),
        }
        shutil.rmtree(reply["trace_dir"], ignore_errors=True)
        shutil.rmtree(frames["trace_dir"], ignore_errors=True)
        yield out
    finally:
        server.stop()


@pytest.mark.parametrize("phase", [p for p in ALL_PHASES if p != "spec_verify"])
def test_phase_is_an_event_in_the_capture(captured, phase):
    names = {name for _, _, name, _ in _engine_events(captured["profile"])}
    assert f"engine.{phase}" in names


def test_phase_events_lie_flat_on_the_owner_thread(captured):
    events = _engine_events(captured["profile"])
    assert len(events) >= 12  # every phase at least once, under any load
    assert len({line for _, _, _, line in events}) == 1  # one thread's line
    # Flat: a parent's event ends where its child's begins, so no two
    # overlap and none encloses a step.
    for (_, end, name, _), (start, _, nxt, _) in zip(events, events[1:]):
        assert start >= end, (name, nxt)
    assert "engine.step" not in {name for _, _, name, _ in events}


def test_capture_holds_no_python_frames_unless_asked(captured):
    assert not any(n.startswith("$") for n in _all_names(captured["profile"]))
    # The body key turns Python's tracer back on for an operator.
    assert any(n.startswith("$") for n in _all_names(captured["frames_profile"]))


def test_benchmark_reduction_labels_gaps_with_phases(captured):
    reduced = bench_trace.reduce(captured["profile"])
    assert reduced is not None and reduced["idle_gaps"]
    labels = [label for label, _ in reduced["idle_gaps"]]
    assert any(label.startswith("engine.") for label in labels), labels
    assert not any(label.startswith(("$", "__unknown__")) for label in labels), labels


def test_phase_counters_and_idle_account_for_the_owner_threads_time(captured):
    grew = {p: captured["after"][_counter(p)] - captured["before"][_counter(p)] for p in LOOP}
    assert grew["idle"] > 0 and grew["readback"] > 0 and grew["prefill"] > 0
    # Closes land in the counters: at each scrape at most one phase is
    # open, so the sum misses the elapsed time by the loop's bookkeeping
    # between steps and a part of one phase.
    assert sum(grew.values()) == pytest.approx(captured["elapsed"], rel=0.05)


def test_sub_phase_seconds_lie_inside_their_parents(captured):
    def grew(phase):
        return captured["after"][_counter(phase)] - captured["before"][_counter(phase)]

    assert 0 < grew("schedule.start_prefill") <= grew("schedule")
    assert 0 < grew("prefill.chunk") + grew("prefill.graft") <= grew("prefill")
    assert 0 < grew("dispatch.frontier") <= grew("dispatch")
    # A slot is cleared inside whichever phase ended its request.
    assert 0 < grew("finish.clear_slot") <= grew("sample") + grew("host_gap") + grew("schedule")
    # The first token's sync nests in prefill (no handoff admission here).
    assert 0 < grew("prefill.first_token") <= grew("prefill")
    assert 0 < grew("prefill.chunk") + grew("prefill.graft") + grew("prefill.first_token") <= grew("prefill")
    for child in ("dispatch.state", "dispatch.enqueue"):
        assert grew(child) > 0, child
    assert grew("dispatch.state") + grew("dispatch.enqueue") + grew("dispatch.frontier") <= grew("dispatch")
    # A realign runs inside the phase that found the finish or discard.
    assert 0 < grew("finish.realign") <= grew("dispatch") + grew("sample") + grew("host_gap")


def test_work_is_counted_where_it_is_timed(captured):
    def grew(name):
        return captured["after"][name] - captured["before"][name]

    finished = grew("tpu_engine_requests_total")
    assert finished >= 2
    assert grew("tpu_engine_cleared_slots_total") == finished
    assert grew("tpu_engine_prefill_chunks_total") >= finished / 2  # one a group
    blocks = grew("tpu_engine_decode_dispatches_block_total")
    singles = grew("tpu_engine_decode_dispatches_step_total")
    assert blocks > 0 and singles > 0  # decode_block=4 of 12 tokens, then the tail
    assert blocks + singles >= grew("tpu_engine_steps_total")
    assert grew("tpu_engine_state_rebuilds_total") > 0
    # One realign a close: every discarded overlap realigns, and so does
    # a finish with nothing in flight.
    realigns = grew("tpu_engine_seq_len_realigns_total")
    assert realigns >= max(grew("tpu_engine_overlap_discards_total"), 1)
    assert realigns <= finished + grew("tpu_engine_overlap_discards_total")


def test_debug_profile_keeps_what_the_benchmark_reads(captured):
    snap = captured["debug_profile"]
    assert snap["steps"] > 0
    for phase in PHASES:
        assert isinstance(snap["phases"][phase]["total_s"], float)
    for phase in (IDLE,) + SUB_PHASES:
        assert snap["phases"][phase]["total_s"] > 0
    assert "trace_overhead" not in snap
    for phase in ALL_PHASES:
        assert isinstance(snap["phases"][phase]["cpu_s"], float)
    assert snap["phases"]["dispatch.enqueue"]["cpu_s"] > 0
    # The host's own work runs on the loop thread: CPU in the counter.
    assert captured["after"]["tpu_engine_loop_host_cpu_seconds_total"] > 0
    # The same closes feed /debug/profile and /metrics.
    assert snap["phases"]["prefill.graft"]["total_s"] == pytest.approx(
        captured["after"][_counter("prefill.graft")], rel=0.2)


# ------------------------------------------------ the profiler by itself ----


class _Sink:
    def __init__(self):
        self.total, self.calls = 0.0, 0

    def inc(self, amount=1.0):
        self.total += amount
        self.calls += 1


def test_a_childs_seconds_count_in_its_parent_and_steps_sum_to_their_wall():
    seconds = {p: _Sink() for p in ALL_PHASES}
    counts = {"prefill.graft": _Sink()}
    prof = EngineProfiler(seconds=seconds, counts=counts)
    prof.begin_step()
    time.sleep(0.002)  # step() before its first phase: counted in it
    with prof.phase("schedule"):
        time.sleep(0.002)
    with prof.phase("prefill"):
        with prof.phase("prefill.graft"):
            time.sleep(0.004)
        with prof.phase("prefill.graft"):
            time.sleep(0.001)
    time.sleep(0.002)  # step()'s wrap-up: counted in the last phase
    wall = prof.finish_step(
        active_slots=1, max_slots=2, queued=0, kv_page_utilization=0.0, tokens=1)
    snap = prof.snapshot()["phases"]
    assert counts["prefill.graft"].calls == 2
    assert 0.005 <= snap["prefill.graft"]["total_s"] <= snap["prefill"]["total_s"]
    assert snap["schedule"]["total_s"] >= 0.004
    assert snap["schedule"]["total_s"] + snap["prefill"]["total_s"] == pytest.approx(wall, abs=2e-4)
    assert seconds["prefill"].total == pytest.approx(snap["prefill"]["total_s"], abs=1e-5)
    assert snap["prefill.graft"]["window_steps"] == 1 and snap["idle"]["window_steps"] == 0


def test_idle_outside_a_step_reaches_totals_and_counter_only():
    seconds = {IDLE: _Sink()}
    prof = EngineProfiler(seconds=seconds)
    with prof.phase(IDLE):
        time.sleep(0.002)
    snap = prof.snapshot()
    assert snap["steps"] == 0 and snap["window"] == 0
    assert seconds[IDLE].total > 0.001
    assert snap["phases"][IDLE]["total_s"] == pytest.approx(seconds[IDLE].total, abs=1e-5)
    with pytest.raises(KeyError):
        prof.phase("no_such_phase")


def test_a_phase_unwinds_when_its_body_raises():
    prof = EngineProfiler()
    prof.begin_step()
    with pytest.raises(RuntimeError):
        with prof.phase("dispatch"):
            with prof.phase("dispatch.frontier"):
                raise RuntimeError("boom")
    prof.finish_step(active_slots=0, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=0)
    with prof.phase("schedule"):  # the stack is empty again
        pass
    assert prof.snapshot()["phases"]["dispatch.frontier"]["window_steps"] == 1


def test_memory_stats_are_not_read_on_every_step(monkeypatch):
    reads = []
    prof = EngineProfiler(summary_every=4, flight=type("F", (), {"record": lambda self, kind, **kw: reads.append(kw)})())
    monkeypatch.setattr(prof, "_memory_bytes", lambda: reads.append("mem") or 123)
    for _ in range(3):
        prof.begin_step()
        prof.finish_step(active_slots=0, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=0)
    assert reads == []  # three steps, no read
    prof.begin_step()
    prof.finish_step(active_slots=0, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=0)
    assert reads[0] == "mem" and reads[1]["mem_bytes"] == 123  # the fourth step's flight summary
    assert prof.snapshot()["device_memory"] == {"bytes_in_use": 123}


def test_annotations_cost_a_flag_test_without_a_capture():
    assert not hasattr(engine_profiler, "trace_active")
    prof = EngineProfiler()
    t0 = time.perf_counter()
    for _ in range(2000):
        with prof.phase("dispatch"):
            pass
    per_phase_us = (time.perf_counter() - t0) / 2000 * 1e6
    assert per_phase_us < 100  # a few microseconds; a 14 ms step holds about ten


def test_a_served_window_leaves_request_spans_and_no_step_spans(captured):
    """The ring is for requests: step() records no span of its own (the
    profiler keeps every step), so a busy loop evicts nothing."""
    spans = captured["spans"]
    assert not [s for s in spans if s["trace_id"] == ENGINE_TRACE and s["name"] == "engine.step"]
    names = {s["name"] for s in spans}
    assert {"request", "queue", "prefill", "decode"} <= names


class _Clock:
    """A thread clock the test moves by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_cpu_goes_flat_to_the_innermost_phase_and_skips_the_chips_waits():
    clock, host_cpu = _Clock(), _Sink()
    prof = EngineProfiler(host_cpu=host_cpu, thread_clock=clock)
    prof.begin_step()
    clock.t += 1.0  # before the first phase: that phase's
    with prof.phase("prefill"):
        clock.t += 2.0
        with prof.phase("prefill.first_token"):
            clock.t += 4.0
        clock.t += 8.0
        with prof.phase("prefill.graft"):
            clock.t += 16.0
    clock.t += 32.0  # between top-level phases: the next one's
    with prof.phase("readback"):
        clock.t += 64.0
    with prof.phase("dispatch"):
        clock.t += 128.0
        with prof.phase("dispatch.enqueue"):
            clock.t += 256.0
    clock.t += 512.0  # step()'s wrap-up: the top-level phase that ran last
    prof.finish_step(active_slots=1, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=1)
    clock.t += 1024.0  # between steps: nobody's
    with prof.phase(IDLE):
        clock.t += 2048.0
    cpu = {p: v["cpu_s"] for p, v in prof.snapshot()["phases"].items()}
    assert cpu["prefill"] == 1 + 2 + 8
    assert cpu["prefill.first_token"] == 4 and cpu["prefill.graft"] == 16
    assert cpu["readback"] == 32 + 64
    assert cpu["dispatch"] == 128 + 512 and cpu["dispatch.enqueue"] == 256
    assert cpu[IDLE] == 2048
    assert sum(cpu.values()) == 4095 - 1024
    # The counter leaves out the waits for the chip (and idle).
    assert host_cpu.total == 1 + 2 + 8 + 16 + 128 + 256 + 512


def test_the_host_cpu_counter_leaves_a_speculative_round_out_whole():
    clock, host_cpu = _Clock(), _Sink()
    prof = EngineProfiler(host_cpu=host_cpu, thread_clock=clock)
    prof.begin_step()
    with prof.phase("spec_verify"):
        clock.t += 1.0
        with prof.phase("finish.clear_slot"):
            clock.t += 2.0
    prof.finish_step(active_slots=1, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=1)
    assert prof.snapshot()["phases"]["finish.clear_slot"]["cpu_s"] == 2
    assert host_cpu.total == 0


def test_cpu_and_wall_partition_the_same_instants():
    """With the wall clock standing in for the thread clock, each phase's
    flat CPU is its self time (its seconds less its children's), and the
    step's CPU sums to its wall."""
    prof = EngineProfiler(thread_clock=time.perf_counter)
    prof.begin_step()
    time.sleep(0.002)
    with prof.phase("schedule"):
        with prof.phase("schedule.start_prefill"):
            time.sleep(0.003)
        time.sleep(0.001)
    time.sleep(0.002)
    with prof.phase("dispatch"):
        with prof.phase("dispatch.state"):
            time.sleep(0.001)
        with prof.phase("dispatch.enqueue"):
            time.sleep(0.002)
        with prof.phase("dispatch.frontier"):
            with prof.phase("finish.clear_slot"):
                time.sleep(0.001)
    time.sleep(0.002)
    wall = prof.finish_step(active_slots=1, max_slots=1, queued=0, kv_page_utilization=0.0, tokens=1)
    phases = prof.snapshot()["phases"]
    wall_s = {p: v["total_s"] for p, v in phases.items()}
    cpu_s = {p: v["cpu_s"] for p, v in phases.items()}
    children = {
        "schedule": ["schedule.start_prefill"],
        "dispatch": ["dispatch.state", "dispatch.enqueue", "dispatch.frontier"],
        "dispatch.frontier": ["finish.clear_slot"],
    }
    for phase in ALL_PHASES:
        own = wall_s[phase] - sum(wall_s[c] for c in children.get(phase, []))
        assert cpu_s[phase] == pytest.approx(own, abs=1e-4), phase
    assert sum(cpu_s.values()) == pytest.approx(wall, abs=1e-4)
    assert wall_s["schedule"] + wall_s["dispatch"] == pytest.approx(wall, abs=2e-4)
