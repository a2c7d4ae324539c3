"""Fleet trace assembler (tools/trace_assemble.py): joining router +
replica span dumps into per-request timelines, orphan/gap/broken-link
verdicts, skew normalization, completeness detections, and the file
loaders.  Pure stdlib — no sockets, no JAX; the live-endpoint mode is
exercised against real router/replica processes in tests/test_router.py
and the chaos suite.  The scorer the chaos suite joins its detections
with (tools/chaos_report.py) is pinned here too."""

from __future__ import annotations

import json

import pytest

from k8s_device_plugin_tpu.utils.spans import SpanRecorder, format_span_id

from tools import chaos_report
from tools import trace_assemble as ta


def span(name, tid, span_id, parent_id=0, start=1000.0, dur=1.0, **attrs):
    entry = {
        "name": name,
        "trace_id": tid,
        "span_id": span_id,
        "parent_id": parent_id,
        "start": start,
        "duration_ms": dur,
    }
    if attrs:
        entry["attrs"] = attrs
    return entry


def router_source(spans, name="router"):
    return {"name": name, "spans": spans, "dropped": 0}


def _happy_sources(tid="t-1"):
    """Router root + 2 attempts (primary died -> failover), replica
    trees under both — the canonical killed-replica shape."""
    router = [
        span(ta.ROOT_SPAN, tid, 1, start=1000.0, dur=500.0,
             outcome="ok", attempts=2, stream=True),
        span("router.route", tid, 2, parent_id=1, start=1000.0, dur=0.1,
             replica="r1:1", placement="home"),
        span(ta.ATTEMPT_SPAN, tid, 3, parent_id=1, start=1000.1, dur=200.0,
             replica="r1:1", attempt=0, kind="primary", status=200,
             outcome="died", tokens=3),
        span("router.route", tid, 4, parent_id=1, start=1200.2, dur=0.1,
             replica="r2:1", placement="failover"),
        span(ta.ATTEMPT_SPAN, tid, 5, parent_id=1, start=1200.3, dur=299.0,
             replica="r2:1", attempt=1, kind="failover", status=200,
             outcome="done", tokens=5),
    ]
    # Replica 1 runs 2.0s of clock skew ahead of the router.
    r1 = [
        span("request", tid, 11, start=1002.2, dur=199.0,
             parent=format_span_id(3), hop=1, attempt=0,
             outcome="cancelled"),
        span("queue", tid, 12, parent_id=11, start=1002.2, dur=0.5),
        span("prefill", tid, 13, parent_id=11, start=1002.7, dur=10.0),
    ]
    r2 = [
        span("request", tid, 21, start=1200.4, dur=298.0,
             parent=format_span_id(5), hop=1, attempt=1,
             outcome="completed"),
        span("decode", tid, 22, parent_id=21, start=1200.5, dur=290.0),
    ]
    return [
        router_source(router),
        router_source(r1, name="replica-1"),
        router_source(r2, name="replica-2"),
    ]


def test_happy_path_single_complete_timeline():
    timelines = ta.assemble(_happy_sources())
    assert len(timelines) == 1
    t = timelines[0]
    assert t["complete"], t
    assert not t["orphans"] and not t["gaps"] and not t["broken_links"]
    assert t["root"]["name"] == ta.ROOT_SPAN
    # Attempts causally ordered, each carrying its replica tree.
    assert [a["attempt"] for a in t["attempts"]] == [0, 1]
    assert [a["kind"] for a in t["attempts"]] == ["primary", "failover"]
    for a in t["attempts"]:
        assert len(a["replica_trees"]) == 1
    # The replica children rode along under their roots.
    names = [c["name"] for c in t["attempts"][0]["replica_trees"][0]["children"]]
    assert names == ["queue", "prefill"]


def test_skew_normalization_nests_replica_inside_attempt():
    t = ta.assemble(_happy_sources())[0]
    a0 = t["attempts"][0]
    # Replica-1's clock ran ~2.1s ahead; the estimated skew removes it
    # so the displayed tree starts AT the attempt's own start.
    assert abs(a0["skew_s"] - (1002.2 - 1000.1)) < 1e-6
    assert abs(a0["replica_trees"][0]["start"] - a0["start"]) < 1e-6
    # In-process offsets inside the replica tree are preserved exactly.
    q = a0["replica_trees"][0]["children"][0]
    assert abs(q["start"] - a0["replica_trees"][0]["start"]) < 1e-6


def test_orphan_when_parent_resolves_nowhere():
    sources = _happy_sources()
    # Corrupt replica-2's parent link.
    sources[2]["spans"][0]["attrs"]["parent"] = format_span_id(999)
    t = ta.assemble(sources)[0]
    assert not t["complete"]
    assert len(t["orphans"]) == 1
    assert "resolves to no router attempt" in t["orphans"][0]["reason"]
    # The failover attempt lost its tree -> ALSO a gap (status 200).
    assert len(t["gaps"]) == 1


def test_orphan_when_hop_context_missing():
    sources = _happy_sources()
    del sources[2]["spans"][0]["attrs"]["parent"]
    t = ta.assemble(sources)[0]
    assert len(t["orphans"]) == 1
    assert "no hop context" in t["orphans"][0]["reason"]


def test_gap_flags_attempt_without_replica_tree():
    sources = _happy_sources()
    sources.pop(2)  # replica-2's dump lost
    t = ta.assemble(sources)[0]
    assert not t["complete"]
    assert [g["attempt"] for g in t["gaps"]] == [1]
    # A rejected attempt (503) expects NO tree: not a gap.
    sources = _happy_sources()
    sources[0]["spans"][4]["attrs"].update(status=503, outcome="draining")
    sources.pop(2)
    t = ta.assemble(sources)[0]
    assert not t["gaps"]


def test_broken_link_when_ring_dropped_parent():
    sources = _happy_sources()
    # The replica ring rolled the request root out; a child survives.
    sources[1]["spans"] = sources[1]["spans"][1:]
    t = ta.assemble(sources)[0]
    assert not t["complete"]
    assert {b["span_id"] for b in t["broken_links"]} == {12, 13}
    assert t["gaps"], "the lost tree is also a gap"


def test_replica_only_assembly_is_standalone_not_orphan():
    sources = _happy_sources()[2:]  # replica-2 alone
    t = ta.assemble(sources)[0]
    assert not t["orphans"] and not t["gaps"]
    assert t["root"] is None and not t["complete"]
    assert len(t["standalone_trees"]) == 1


def test_completeness_detections_and_attempt_count_gate():
    timelines = ta.assemble(_happy_sources())
    det = ta.completeness_detections(timelines)
    assert len(det) == 1 and det[0]["cls"] == "trace_complete"
    assert det[0]["rid"] == "t-1"
    # Attempt-count gate: the router metered 2 legs; a claim of 3 is a
    # completeness miss even with a structurally clean tree.
    assert ta.completeness_detections(timelines, {"t-1": 2})
    assert not ta.completeness_detections(timelines, {"t-1": 3})
    # An incomplete timeline never emits a detection.
    broken = ta.assemble(_happy_sources()[:2])
    assert not ta.completeness_detections(broken)


def test_detections_join_with_chaos_report_scoring():
    timelines = ta.assemble(_happy_sources())
    injected = [
        {"cls": "trace_complete", "rid": "t-1", "t0": 999.0, "t1": 1600.0},
        {"cls": "trace_complete", "rid": "t-GONE", "t0": 999.0, "t1": 1600.0},
    ]
    score = chaos_report.score_detections(
        injected, ta.completeness_detections(timelines), grace_s=1.0
    )
    cls = score["per_class"]["trace_complete"]
    assert cls["tp"] == 1 and cls["fn"] == 1 and cls["fp"] == 0
    assert cls["precision"] == 1.0 and cls["recall"] == 0.5


def test_chaos_report_scoring_and_summary():
    """tools/chaos_report.py: the precision/recall join semantics the
    scenario matrix depends on — window+key matching, multi-report
    faults not double-counted as FPs, worst-class summary — pinned
    hermetically (no fleet needed)."""
    injected = [
        {"cls": "chip_unplug", "node": 0, "device": "tpu-1",
         "t0": 100.0, "t1": 101.0},
        {"cls": "chip_unplug", "node": 2, "device": "tpu-3",
         "t0": 100.0, "t1": 101.0},
    ]
    detected = [
        # Matches fault 1 (in window, keys agree)...
        {"cls": "chip_unplug", "node": 0, "device": "tpu-1", "ts": 100.4},
        # ...a cooldown re-fire of the SAME fault: matched window, not FP.
        {"cls": "chip_unplug", "node": 0, "device": "tpu-1", "ts": 100.9},
        # A detection nothing injected: false positive.
        {"cls": "chip_unplug", "node": 5, "device": "tpu-0", "ts": 100.5},
    ]
    score = chaos_report.score_detections(injected, detected, grace_s=1.0)
    c = score["per_class"]["chip_unplug"]
    assert (c["tp"], c["fp"], c["fn"]) == (1, 1, 1)
    assert c["precision"] == pytest.approx(2 / 3)
    assert c["recall"] == pytest.approx(0.5)
    assert c["latency_p50_s"] == pytest.approx(0.4)
    results = [
        {"scenario": "s1", "score": score, "slo": {"pass": True},
         "pass": False},
        {"scenario": "s2",
         "score": chaos_report.score_detections(
             [{"cls": "drift", "t0": 0.0, "t1": 1.0}],
             [{"cls": "drift", "ts": 0.5}],
         ),
         "slo": {"pass": False}, "pass": True},
    ]
    summary = chaos_report.chaos_summary(results)
    assert summary["scenarios"] == 2
    assert summary["passed"] == 1
    assert summary["precision"] == pytest.approx(2 / 3, abs=1e-3)  # worst class
    assert summary["recall"] == 0.5  # worst class
    assert summary["slo_pass"] is False
    matrix = chaos_report.render_matrix(results)
    assert "| s1 | chip_unplug |" in matrix
    assert "| s2 | drift |" in matrix
    row = chaos_report.ledger_row(results)
    assert "1/2 scenarios" in row and "SLO FAIL" in row


def test_engine_and_daemon_traces_are_not_timelines():
    sources = [router_source([
        span("engine.step", "engine", 1),
        span("rpc.Allocate", "daemon", 2),
        span("request", "real-req", 3, outcome="completed"),
    ])]
    assert ta.trace_ids(sources) == ["real-req"]


def test_real_recorders_round_trip_through_dump_files(tmp_path):
    """The wire contract end to end, no sockets: real SpanRecorders on
    both sides, the flight-dump file format in the middle."""
    from k8s_device_plugin_tpu.utils import flight as flight_mod

    tid = "round-trip"
    router_rec = SpanRecorder(name="router")
    root = router_rec.reserve_id()
    leg = router_rec.reserve_id()
    t0 = __import__("time").monotonic()
    replica_rec = SpanRecorder(name="engine")
    rroot = replica_rec.reserve_id()
    replica_rec.record_span(
        "request", tid, start_monotonic=t0, span_id=rroot,
        attrs={"parent": format_span_id(leg), "hop": 1, "attempt": 0,
               "outcome": "completed"},
    )
    replica_rec.record_span(
        "decode", tid, start_monotonic=t0, parent_id=rroot,
    )
    router_rec.record_span(
        "router.attempt", tid, start_monotonic=t0, span_id=leg,
        parent_id=root,
        attrs={"replica": "r:1", "attempt": 0, "kind": "primary",
               "status": 200, "outcome": "done"},
    )
    router_rec.record_span(
        "router.request", tid, start_monotonic=t0, span_id=root,
        attrs={"outcome": "ok", "attempts": 1},
    )
    path_r = flight_mod.dump_all(
        str(tmp_path), reason="router", recorders=[], span_recorders=[router_rec]
    )
    path_e = flight_mod.dump_all(
        str(tmp_path), reason="engine", recorders=[], span_recorders=[replica_rec]
    )
    sources = ta.load_file(path_r) + ta.load_file(path_e)
    timelines = ta.assemble(sources)
    assert len(timelines) == 1 and timelines[0]["complete"]
    tree = timelines[0]["attempts"][0]["replica_trees"][0]
    assert [c["name"] for c in tree["children"]] == ["decode"]
    # Text rendering names the verdict and every layer.
    text = ta.render_text(timelines[0])
    assert "complete" in text and "router.request" in text
    assert "attempt#0" in text and "decode" in text


def test_loader_accepts_debug_spans_and_bare_list_shapes(tmp_path):
    payloads = {
        "debug_spans.json": {"name": "eng", "spans": [span("request", "x", 1)],
                             "dropped": 2, "capacity": 512},
        "debug_state.json": {"engine": {}, "spans": [span("queue", "x", 2)],
                             "spans_dropped": 0},
        "bare.json": [span("decode", "x", 3)],
    }
    sources = []
    for fname, payload in payloads.items():
        p = tmp_path / fname
        p.write_text(json.dumps(payload))
        sources.extend(ta.load_file(str(p)))
    assert {s["name"] for s in sources} == {
        "eng", str(tmp_path / "debug_state.json"), str(tmp_path / "bare.json")
    }
    assert sources[0]["dropped"] == 2


def test_cli_main_renders_and_writes_json(tmp_path, capsys):
    sources = _happy_sources()
    paths = []
    for i, src in enumerate(sources):
        p = tmp_path / f"src{i}.json"
        p.write_text(json.dumps({"name": src["name"], "spans": src["spans"]}))
        paths.append(str(p))
    out_json = tmp_path / "timelines.json"
    rc = ta.main(paths + ["--json", str(out_json)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "1 complete, 0 incomplete" in out
    data = json.loads(out_json.read_text())
    assert data["timelines"][0]["trace_id"] == "t-1"
    # --rid narrows to one trace; unknown rid -> one empty timeline.
    rc = ta.main(paths + ["--rid", "t-1"])
    assert rc == 0
    assert "trace t-1" in capsys.readouterr().out
    # No sources at all is an operator error.
    assert ta.main([]) == 2
