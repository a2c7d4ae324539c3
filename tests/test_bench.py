"""bench.py and chip_smoke.py measure the chip or say that they cannot:
without a TPU both exit non-zero and print no result (a number from the
CPU is never written under a device metric's name).  The tools/bench_diff.py
record differ is pinned below."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _run(argv, cwd=REPO_ROOT, **env):
    return subprocess.run(
        [sys.executable, *argv],
        cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_bench_exits_nonzero_without_a_tpu():
    proc = _run([os.path.join(REPO_ROOT, "bench.py")])
    assert proc.returncode == 2
    assert proc.stdout.strip() == "", "no result line without a chip"
    assert "platform 'cpu'" in proc.stderr and "refusing" in proc.stderr


def test_chip_smoke_refuses_without_a_tpu_and_runs_no_leg():
    """JAX_PLATFORMS=cpu: the probe names the platform it found, no leg
    runs, the exit code is non-zero, and the parent never imported jax."""
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main()\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "sys.exit(rc)\n"
    )
    proc = _run(["-c", code], PYTHONPATH=REPO_ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "platform 'cpu'" in proc.stdout
    legs = [l for l in proc.stdout.splitlines() if l.startswith("--- leg")]
    assert legs == ["--- leg probe"]
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
    assert "no leg was run" in proc.stderr


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "run it from a checkout" in proc.stderr


def test_chip_smoke_serves_the_shipped_geometry():
    """The smoke's flags ARE the deployed replica's: every one of them
    appears in deploy/k8s-deploy-serve-http.yaml (the port aside)."""
    import chip_smoke

    with open(os.path.join(REPO_ROOT, "deploy", "k8s-deploy-serve-http.yaml")) as f:
        manifest = f.read()
    for flag in chip_smoke.SERVE_FLAGS:
        if not flag.startswith("--http-port"):
            assert f'"{flag}"' in manifest, flag


def test_bench_diff_ignores_unknown_daemon_metric_blocks(tmp_path):
    """The daemon-side attribution metrics (PR 5) do not ride in BENCH
    records; a record that nonetheless carries unknown parsed blocks
    (e.g. a future "attribution" section) must diff and row identically
    to one without — no schema break in tools/bench_diff.py."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 6,
        "rc": 0,
        "parsed": {"metric": "resnet50_images_per_sec_per_chip",
                   "value": 1500.0, "unit": "images/sec/chip",
                   "vs_baseline": 1.0, "platform": "tpu"},
    }
    noisy = json.loads(json.dumps(base))
    noisy["parsed"]["attribution"] = {
        "attributed_chips": 4, "drift_total": 0, "podresources_up": 1,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(noisy))
    plain = bench_diff.load_record(str(tmp_path / "a.json"))
    extra = bench_diff.load_record(str(tmp_path / "b.json"))
    # The unknown block is ignored wholesale: identical normalized
    # fields (the raw "parsed" blob is carried but never diffed),
    # identical diff output, identical ledger-row payload.
    for rec in (plain, extra):
        rec.pop("path"), rec.pop("parsed")
    assert plain == extra
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "attribution" not in diff
    assert "*" not in diff.replace("->", "")  # no field marked changed
    assert "attribution" not in bench_diff.ledger_row(a, b)


def test_bench_diff_parses_chaos_block(tmp_path):
    """Records grew a CHAOS block (ISSUE 7, tools/chaos_report.py
    chaos_summary): scenario counts plus the WORST per-class detector
    precision/recall and the SLO verdict must surface in the normalized
    record, the field diff, and the ledger row — a precision sag or an
    SLO flip between rounds is the detector-regression tell."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 6,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    chaotic = json.loads(json.dumps(base))
    chaotic["n"] = 7
    chaotic["parsed"]["chaos"] = {
        "scenarios": 4, "passed": 4, "faults_injected": 12,
        "precision": 0.92, "recall": 1.0, "slo_pass": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(chaotic))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["chaos_scenarios"] == 4
    assert b["chaos_passed"] == 4
    assert b["chaos_faults"] == 12
    assert b["chaos_precision"] == 0.92
    assert b["chaos_recall"] == 1.0
    assert b["chaos_slo_pass"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "chaos_precision" in diff
    row = bench_diff.ledger_row(a, b)
    assert "chaos 4/4" in row and "p 0.92" in row
    assert "SLO-FAIL" not in row
    # An SLO-failing round screams in the row.
    chaotic["parsed"]["chaos"]["slo_pass"] = False
    (tmp_path / "c.json").write_text(json.dumps(chaotic))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "SLO-FAIL" in bench_diff.ledger_row(a, c)


def test_chaos_report_scoring_and_summary(tmp_path):
    """tools/chaos_report.py: the precision/recall join semantics the
    scenario matrix depends on — window+key matching, multi-report
    faults not double-counted as FPs, worst-class summary — pinned
    hermetically (no fleet needed)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_report", os.path.join(REPO_ROOT, "tools", "chaos_report.py")
    )
    chaos_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos_report)

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


def test_bench_diff_parses_tp_block(tmp_path):
    """Serving records grew a MULTICHIP tensor-parallel block (ISSUE 6):
    tp size, decode tokens/s under tp, scaling efficiency, discards, and
    the bit-identity flag must surface in the normalized record, the
    field diff, and the ledger row — the efficiency collapse (or a
    tokens_match flip) is the regression tell bench rounds watch."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 5,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    tp = json.loads(json.dumps(base))
    tp["n"] = 6
    tp["parsed"]["tp"] = {
        "size": 2, "tokens_per_sec": 170.0, "tp1_tokens_per_sec": 100.0,
        "speedup": 1.7, "scaling_efficiency": 0.85, "discards": 3,
        "tokens_match": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(tp))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["tp_size"] == 2
    assert b["tp_tokens_per_sec"] == 170.0
    assert b["tp_scaling_efficiency"] == 0.85
    assert b["tp_discards"] == 3
    assert b["tp_tokens_match"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "tp_scaling_efficiency" in diff
    row = bench_diff.ledger_row(a, b)
    assert "tp=2" in row and "eff 0.85" in row
    assert "DIVERGED" not in row
    # A diverged round screams in the row.
    tp["parsed"]["tp"]["tokens_match"] = False
    (tmp_path / "c.json").write_text(json.dumps(tp))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "DIVERGED" in bench_diff.ledger_row(a, c)


def test_bench_diff_parses_router_block(tmp_path):
    """Serving records grew a ROUTER block (ISSUE 8): replica count,
    affinity vs random-control KV hit rates and TTFT p99, home rate,
    and dropped streams must surface in the normalized record, the
    field diff, and the ledger row — the affinity hit rate collapsing
    toward the control (or any dropped stream) is the regression tell."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 7,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    routed = json.loads(json.dumps(base))
    routed["n"] = 8
    routed["parsed"]["router"] = {
        "replicas": 2, "requests": 32, "sessions": 4,
        "affinity": {"prefix_hits": 96, "hit_rate": 3.0,
                     "ttft_p99_ms": 41.5, "home_rate": 0.97,
                     "dropped": 0, "failovers": 0, "retries": 0},
        "random": {"prefix_hits": 16, "hit_rate": 0.5,
                   "ttft_p99_ms": 63.2, "home_rate": 0.0,
                   "dropped": 0, "failovers": 0, "retries": 0},
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(routed))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["router_replicas"] == 2
    assert b["router_affinity_hit_rate"] == 3.0
    assert b["router_affinity_ttft_p99_ms"] == 41.5
    assert b["router_home_rate"] == 0.97
    assert b["router_random_hit_rate"] == 0.5
    assert b["router_random_ttft_p99_ms"] == 63.2
    assert b["router_dropped"] == 0
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "router_affinity_hit_rate" in diff
    row = bench_diff.ledger_row(a, b)
    assert "router K=2" in row and "3.0 hits/req" in row
    assert "vs random 0.5" in row
    assert "DROPPED" not in row  # zero drops stay quiet
    # Any dropped stream screams in the row.
    routed["parsed"]["router"]["affinity"]["dropped"] = 2
    (tmp_path / "c.json").write_text(json.dumps(routed))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "DROPPED 2" in bench_diff.ledger_row(a, c)


def test_bench_diff_parses_fabric_block(tmp_path):
    """Records grew a FABRIC block (ISSUE 18, benchmark.py
    _run_fabric_phase): fleet hit rate, TTFT p99, and cross-peer pull
    count vs the affinity-only control must surface in the normalized
    record, the field diff, and the ledger row — and the row must
    scream when the any-peer pull path stops moving pages
    (cross_peer_pulls 0 — NO-FABRIC-HITS) or locating costs more than
    it saves (fabric p99 > 1.2x control — FABRIC-TTFT-REGRESSED)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 17,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    fabbed = json.loads(json.dumps(base))
    fabbed["n"] = 18
    fabbed["parsed"]["fabric"] = {
        "replicas": 3, "requests": 32, "sessions": 8,
        "shared_prefix_len": 16,
        "fabric": {"fleet_hits": 120, "hit_rate": 3.75,
                   "ttft_p99_ms": 234.0, "cross_peer_pulls": 2,
                   "dropped": 0},
        "control": {"fleet_hits": 116, "hit_rate": 3.62,
                    "ttft_p99_ms": 238.0, "cross_peer_pulls": 0,
                    "dropped": 0},
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(fabbed))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["fabric_hit_rate"] == 3.75
    assert b["fabric_ttft_p99_ms"] == 234.0
    assert b["fabric_cross_peer_pulls"] == 2
    assert b["fabric_control_hit_rate"] == 3.62
    assert b["fabric_control_ttft_p99_ms"] == 238.0
    assert b["fabric_dropped"] == 0
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "fabric_hit_rate" in diff
    assert "fabric_cross_peer_pulls" in diff
    row = bench_diff.ledger_row(a, b)
    assert "fabric 3.75 hits/req" in row and "(2 pulls)" in row
    assert "vs control 3.62" in row
    assert "NO-FABRIC-HITS" not in row
    assert "FABRIC-TTFT-REGRESSED" not in row
    # Zero cross-peer pulls: the fabric is silently affinity-only.
    fabbed["parsed"]["fabric"]["fabric"]["cross_peer_pulls"] = 0
    (tmp_path / "c.json").write_text(json.dumps(fabbed))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "NO-FABRIC-HITS" in bench_diff.ledger_row(a, c)
    # Fabric TTFT past 1.2x the control: locating costs more than it
    # saves.
    fabbed["parsed"]["fabric"]["fabric"]["cross_peer_pulls"] = 2
    fabbed["parsed"]["fabric"]["fabric"]["ttft_p99_ms"] = 300.0
    (tmp_path / "d.json").write_text(json.dumps(fabbed))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "FABRIC-TTFT-REGRESSED" in bench_diff.ledger_row(a, d)


def test_bench_diff_parses_overload_block(tmp_path):
    """Records grew an OVERLOAD block (ISSUE 9, benchmark.py
    _run_overload_phase): goodput ratio, shed count, and the
    high-priority-TTFT storm/unloaded ratio must surface in the
    normalized record, the field diff, and the ledger row — and the
    row must scream when priority admission stops protecting the high
    class (ratio > 1.2) or a shed leaks pages (pool_exact false)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 8,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 9
    loaded["parsed"]["overload"] = {
        "storm_requests": 20, "goodput_ratio": 0.91, "sheds": 4,
        "sheds_by_kind": {"expired": 4},
        "hi_ttft_p99_ratio": 1.05, "hi_ttft_p99_storm_ms": 12.5,
        "pool_exact": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["overload_goodput_ratio"] == 0.91
    assert b["overload_sheds"] == 4
    assert b["overload_hi_ttft_ratio"] == 1.05
    assert b["overload_pool_exact"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "overload_goodput_ratio" in diff
    row = bench_diff.ledger_row(a, b)
    assert "overload goodput 0.91" in row and "hi-p99 1.05x" in row
    assert "HI-TTFT-REGRESSED" not in row and "PAGE-LEAK" not in row
    # A round where the high class lost its protection screams...
    loaded["parsed"]["overload"]["hi_ttft_p99_ratio"] = 1.4
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "HI-TTFT-REGRESSED" in bench_diff.ledger_row(a, c)
    # ...and so does a shed that leaked pages.
    loaded["parsed"]["overload"]["hi_ttft_p99_ratio"] = 1.0
    loaded["parsed"]["overload"]["pool_exact"] = False
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "PAGE-LEAK" in bench_diff.ledger_row(a, d)


def test_bench_diff_parses_slo_block(tmp_path):
    """Records grew an SLO block (ISSUE 16, benchmark.py
    _run_slo_phase): the slo-on vs slo-off accounting overhead, the
    verdict count, and the burn-alert self-check must surface in the
    normalized record, the field diff, and the ledger row — and the
    row must scream SLO-OVERHEAD past 1% and BURN-ALERT-MISSED when
    the synthetic burn fails to fire the page rule."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 8,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 9
    loaded["parsed"]["slo"] = {
        "overhead": 0.004, "off_tokens_per_sec": 101.0,
        "on_tokens_per_sec": 100.6, "sli_verdicts": 24,
        "tenants_metered": 1, "burn_alert_fired": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["slo_overhead"] == 0.004
    assert b["slo_verdicts"] == 24
    assert b["slo_burn_alert_fired"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "slo_overhead" in diff and "slo_burn_alert_fired" in diff
    row = bench_diff.ledger_row(a, b)
    assert "slo overhead 0.004" in row and "24 verdicts" in row
    assert "SLO-OVERHEAD" not in row and "BURN-ALERT-MISSED" not in row
    # Accounting past 1% per token screams...
    loaded["parsed"]["slo"]["overhead"] = 0.03
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "SLO-OVERHEAD" in bench_diff.ledger_row(a, c)
    # ...and a dead pager screams loudest.
    loaded["parsed"]["slo"]["overhead"] = 0.004
    loaded["parsed"]["slo"]["burn_alert_fired"] = False
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "BURN-ALERT-MISSED" in bench_diff.ledger_row(a, d)


def test_bench_diff_parses_canary_block(tmp_path):
    """Records grew a CANARY block (ISSUE 17, benchmark.py
    _run_canary_phase): the prober-on vs prober-off serving overhead,
    probe count, and the injected-corruption detection self-check must
    surface in the normalized record, the field diff, and the ledger
    row — and the row must scream PROBE-OVERHEAD past 1% and
    MISMATCH-MISSED when the self-check's corruption went undetected
    (a blind canary is the worst correctness-plane regression)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 8,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 9
    loaded["parsed"]["canary"] = {
        "overhead": 0.006, "tokens_per_sec_canary": 99.4,
        "tokens_per_sec_control": 100.0, "probes": 14,
        "mismatch_detected": True, "fences": 1,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["canary_overhead"] == 0.006
    assert b["canary_probes"] == 14
    assert b["canary_mismatch_detected"] is True
    assert b["canary_fences"] == 1
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "canary_overhead" in diff and "canary_mismatch_detected" in diff
    row = bench_diff.ledger_row(a, b)
    assert "canary overhead 0.006" in row and "14 probes" in row
    assert "PROBE-OVERHEAD" not in row and "MISMATCH-MISSED" not in row
    # Probing past 1% of serving throughput screams...
    loaded["parsed"]["canary"]["overhead"] = 0.02
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "PROBE-OVERHEAD" in bench_diff.ledger_row(a, c)
    # ...and a blind canary screams loudest.
    loaded["parsed"]["canary"]["overhead"] = 0.006
    loaded["parsed"]["canary"]["mismatch_detected"] = False
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "MISMATCH-MISSED" in bench_diff.ledger_row(a, d)


def test_bench_diff_parses_postmortem_block(tmp_path):
    """Records grew a POSTMORTEM block (ISSUE 20, benchmark.py
    _run_postmortem_phase): the collector-armed vs collector-off
    serving overhead and the capture/classification self-check must
    surface in the normalized record, the field diff, and the ledger
    row — and the row must scream CAPTURE-OVERHEAD past 1%,
    CAPTURE-MISSED when the injected incident produced no bundle, and
    ROOTCAUSE-WRONG when the on-disk bundle misclassified."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 8,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 9
    loaded["parsed"]["postmortem"] = {
        "overhead": 0.004, "tokens_per_sec_postmortem": 99.6,
        "tokens_per_sec_control": 100.0, "captures": 1,
        "bundle_found": True, "root_cause": "watchdog_hang",
        "rootcause_ok": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["postmortem_overhead"] == 0.004
    assert b["postmortem_captures"] == 1
    assert b["postmortem_bundle_found"] is True
    assert b["postmortem_root_cause"] == "watchdog_hang"
    assert b["postmortem_rootcause_ok"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "postmortem_overhead" in diff
    assert "postmortem_root_cause" in diff
    row = bench_diff.ledger_row(a, b)
    assert "postmortem overhead 0.004" in row
    assert "1 bundles" in row and "root watchdog_hang" in row
    for scream in ("CAPTURE-OVERHEAD", "CAPTURE-MISSED",
                   "ROOTCAUSE-WRONG"):
        assert scream not in row
    # Capture past 1% of serving throughput screams...
    loaded["parsed"]["postmortem"]["overhead"] = 0.02
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "CAPTURE-OVERHEAD" in bench_diff.ledger_row(a, c)
    # ...a black box that recorded nothing screams...
    loaded["parsed"]["postmortem"]["overhead"] = 0.004
    loaded["parsed"]["postmortem"]["bundle_found"] = False
    loaded["parsed"]["postmortem"]["captures"] = 0
    loaded["parsed"]["postmortem"]["root_cause"] = None
    loaded["parsed"]["postmortem"]["rootcause_ok"] = False
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    row_d = bench_diff.ledger_row(a, d)
    assert "CAPTURE-MISSED" in row_d and "ROOTCAUSE-WRONG" in row_d
    # ...and a wrong verdict screams even when a bundle landed.
    loaded["parsed"]["postmortem"]["bundle_found"] = True
    loaded["parsed"]["postmortem"]["captures"] = 1
    loaded["parsed"]["postmortem"]["root_cause"] = "overload_shed_storm"
    (tmp_path / "e.json").write_text(json.dumps(loaded))
    e = bench_diff.load_record(str(tmp_path / "e.json"))
    row_e = bench_diff.ledger_row(a, e)
    assert "ROOTCAUSE-WRONG" in row_e and "CAPTURE-MISSED" not in row_e


def test_bench_diff_parses_restart_block(tmp_path):
    """Records grew a RESTART block (ISSUE 10, benchmark.py
    _run_restart_phase): cold vs warm post-restart TTFT p99 and the
    restored-page count must surface in the normalized record, the
    field diff, and the ledger row — and the row must scream
    COLD-REGRESSED when the warm restart is SLOWER than a cold one
    (speedup < 1) and NO-RESTORE when the snapshot stopped
    rehydrating (0 pages restored)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 9,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 10
    loaded["parsed"]["restart"] = {
        "sessions": 4, "prefix_tokens": 48,
        "snapshot_bytes": 120000, "snapshot_entries": 3,
        "entries_loaded": 3,
        "cold": {"ttft_p50_ms": 30.0, "ttft_p99_ms": 42.0,
                 "prefix_hits": 0},
        "warm": {"ttft_p50_ms": 12.0, "ttft_p99_ms": 20.0,
                 "prefix_hits": 8, "restored_pages": 12},
        "warm_speedup": 2.1,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["restart_cold_ttft_p99_ms"] == 42.0
    assert b["restart_warm_ttft_p99_ms"] == 20.0
    assert b["restart_restored_pages"] == 12
    assert b["restart_warm_speedup"] == 2.1
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "restart_warm_ttft_p99_ms" in diff
    row = bench_diff.ledger_row(a, b)
    assert "restart warm p99 20.0ms vs cold 42.0ms" in row
    assert "12 pages restored" in row
    assert "COLD-REGRESSED" not in row and "NO-RESTORE" not in row
    # Warm slower than cold: the one outcome worse than no snapshot.
    loaded["parsed"]["restart"]["warm_speedup"] = 0.8
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "COLD-REGRESSED" in bench_diff.ledger_row(a, c)
    # Zero restored pages: the snapshot silently stopped rehydrating.
    loaded["parsed"]["restart"]["warm_speedup"] = 2.1
    loaded["parsed"]["restart"]["warm"]["restored_pages"] = 0
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "NO-RESTORE" in bench_diff.ledger_row(a, d)
    # A skipped phase rides in parsed untouched, never in the row.
    loaded["parsed"]["restart"] = {"skipped": "prompt too short"}
    (tmp_path / "e.json").write_text(json.dumps(loaded))
    e = bench_diff.load_record(str(tmp_path / "e.json"))
    assert "restart_warm_ttft_p99_ms" not in e
    assert "restart warm p99" not in bench_diff.ledger_row(a, e)


def test_bench_diff_parses_elastic_block(tmp_path):
    """Records grew an ELASTIC block (ISSUE 14, benchmark.py
    _run_elastic_phase): cold-join vs peer-warmed-join TTFT p99 and
    the shipped-entry count must surface in the normalized record, the
    field diff, and the ledger row — and the row must scream NO-WARMUP
    when the warmed join is SLOWER than a cold one (warmed_speedup < 1)
    and NO-TRANSFER when the peer stream stopped rehydrating (0
    entries restored)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 13,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 14
    loaded["parsed"]["elastic"] = {
        "sessions": 4, "prefix_tokens": 48,
        "wire_bytes": 98304, "entries": 3, "entries_restored": 3,
        "cold_join": {"ttft_p50_ms": 31.0, "ttft_p99_ms": 44.0,
                      "prefix_hits": 0},
        "warmed_join": {"ttft_p50_ms": 13.0, "ttft_p99_ms": 21.0,
                        "prefix_hits": 8, "restored_pages": 12},
        "warmed_speedup": 2.1,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["elastic_cold_ttft_p99_ms"] == 44.0
    assert b["elastic_warmed_ttft_p99_ms"] == 21.0
    assert b["elastic_entries_restored"] == 3
    assert b["elastic_warmed_speedup"] == 2.1
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "elastic_warmed_ttft_p99_ms" in diff
    row = bench_diff.ledger_row(a, b)
    assert "elastic warmed-join p99 21.0ms vs cold 44.0ms" in row
    assert "3 entries shipped" in row
    assert "NO-WARMUP" not in row and "NO-TRANSFER" not in row
    # Warmed join slower than cold: peer warm-up is actively hurting.
    loaded["parsed"]["elastic"]["warmed_speedup"] = 0.9
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "NO-WARMUP" in bench_diff.ledger_row(a, c)
    # Zero entries over the wire: the transfer silently stopped.
    loaded["parsed"]["elastic"]["warmed_speedup"] = 2.1
    loaded["parsed"]["elastic"]["entries_restored"] = 0
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "NO-TRANSFER" in bench_diff.ledger_row(a, d)
    # A skipped phase rides in parsed untouched, never in the row.
    loaded["parsed"]["elastic"] = {"skipped": "prompt too short"}
    (tmp_path / "e.json").write_text(json.dumps(loaded))
    e = bench_diff.load_record(str(tmp_path / "e.json"))
    assert "elastic_warmed_ttft_p99_ms" not in e
    assert "elastic warmed-join" not in bench_diff.ledger_row(a, e)


def test_bench_diff_parses_trace_block(tmp_path):
    """Records grew a TRACE block (ISSUE 12, benchmark.py's tracing
    phase): the measured spans-on vs spans-off overhead fraction must
    surface in the normalized record, the field diff, and the ledger
    row — and the row must scream TRACE-OVERHEAD when the always-on
    span layer stops being ~free (overhead > 2%)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 11,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 12
    loaded["parsed"]["trace"] = {
        "overhead": 0.004,
        "off_tokens_per_sec": 101.0,
        "on_tokens_per_sec": 100.6,
        "spans_recorded": 64,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["trace_overhead"] == 0.004
    assert b["trace_spans"] == 64
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "trace_overhead" in diff
    row = bench_diff.ledger_row(a, b)
    assert "trace overhead 0.004" in row
    assert "64 spans" in row
    assert "TRACE-OVERHEAD" not in row
    # Overhead past ~2%: the row screams.
    loaded["parsed"]["trace"]["overhead"] = 0.031
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "TRACE-OVERHEAD" in bench_diff.ledger_row(a, c)
    # A record without the block: no trace fields, no row segment.
    assert "trace_overhead" not in a
    assert "trace overhead" not in bench_diff.ledger_row(a, a)


def test_bench_diff_parses_kernels_block(tmp_path):
    """Records grew a KERNELS block (ISSUE 13, benchmark.py
    _run_kernels_phase): per-shape split-K-kernel-vs-gather ratios, the
    minimum, and the fused int8-vs-bf16 ratio must surface in the
    normalized record, the field diff, and the ledger row — and the row
    must scream KERNEL-REGRESSED naming any shape whose ratio fell past
    its recorded value (beyond the 10% jitter tolerance) and
    KERNEL-SLOWER-THAN-GATHER when the minimum drops below 1.0 (the
    state the old single-pass rows were stuck in)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    def shape(ratio):
        return {"fmt": "f32", "splits": 1, "kernel_ms": 0.2,
                "gather_ms": 0.2 * ratio, "single_ms": 2.0,
                "kernel_vs_gather": ratio, "single_vs_gather": 0.1}

    base = {
        "n": 12,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu",
                   "kernels": {
                       "generation": "cpu",
                       "shapes": {"b4_gqa_f32": shape(1.9),
                                  "b4_gqa_int8": shape(1.8)},
                       "min_kernel_vs_gather": 1.8,
                       "int8_vs_bf16": 1.07,
                   }},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 13
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["kernels_min_ratio"] == 1.8
    assert b["kernels_int8_vs_bf16"] == 1.07
    assert b["kernels_shapes"]["b4_gqa_f32"] == 1.9
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "kernels_min_ratio" in diff and "kernels[b4_gqa_f32]" in diff
    row = bench_diff.ledger_row(a, b)
    assert "kernels min 1.8x vs gather" in row
    assert "int8/bf16 1.07x" in row
    assert "KERNEL-REGRESSED" not in row
    assert "KERNEL-SLOWER-THAN-GATHER" not in row
    # One shape regresses past its recorded ratio (beyond tolerance):
    # the row names it; a within-tolerance wobble on the other is quiet.
    worse = json.loads(json.dumps(loaded))
    worse["parsed"]["kernels"]["shapes"]["b4_gqa_f32"] = shape(1.2)
    worse["parsed"]["kernels"]["shapes"]["b4_gqa_int8"] = shape(1.75)
    worse["parsed"]["kernels"]["min_kernel_vs_gather"] = 1.2
    (tmp_path / "c.json").write_text(json.dumps(worse))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    row_c = bench_diff.ledger_row(a, c)
    assert "KERNEL-REGRESSED(b4_gqa_f32)" in row_c
    assert "b4_gqa_int8" not in row_c.split("KERNEL-REGRESSED")[1]
    assert "! KERNEL-REGRESSED b4_gqa_f32" in "\n".join(
        bench_diff.diff_lines(a, c)
    )
    # The minimum below 1.0: slower than the fallback it exists to beat.
    slower = json.loads(json.dumps(loaded))
    slower["parsed"]["kernels"]["min_kernel_vs_gather"] = 0.8
    (tmp_path / "d.json").write_text(json.dumps(slower))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "KERNEL-SLOWER-THAN-GATHER" in bench_diff.ledger_row(a, d)
    # A record without the block: no kernels fields, no row segment.
    blockless = {"n": 1, "rc": 0, "parsed": {"metric": "m", "value": 1.0,
                                             "unit": "u", "platform": "cpu"}}
    (tmp_path / "e.json").write_text(json.dumps(blockless))
    e = bench_diff.load_record(str(tmp_path / "e.json"))
    assert "kernels_min_ratio" not in e
    assert "kernels min" not in bench_diff.ledger_row(e, e)


def test_bench_diff_parses_disagg_block(tmp_path):
    """Records grew a DISAGG block (ISSUE 15, benchmark.py
    _run_disagg_phase): decode ITL p99 flat-vs-growing under prefill
    load must surface in the normalized record, the field diff, and the
    ledger row — the row screams ITL-REGRESSED when the disagg decode
    p99 grows past 1.2x of its unloaded value, NO-HANDOFF when zero
    entries moved over the wire, and DIVERGED when the handed-off
    tokens stop matching the local-prefill oracle."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 15,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 16
    loaded["parsed"]["disagg"] = {
        "prefill_jobs": 4,
        "itl_p99_unloaded_ms": 10.0,
        "unified": {"itl_p99_loaded_ms": 25.0, "ratio": 2.5},
        "disagg": {"itl_p99_loaded_ms": 11.0, "ratio": 1.1,
                   "handoff_entries": 12, "tokens_match": True},
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["disagg_ratio"] == 1.1
    assert b["disagg_unified_ratio"] == 2.5
    assert b["disagg_handoff_entries"] == 12
    assert b["disagg_tokens_match"] is True
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "disagg_ratio" in diff
    row = bench_diff.ledger_row(a, b)
    assert "disagg decode p99 11.0ms under prefill load" in row
    assert "12 entries shipped" in row
    assert "ITL-REGRESSED" not in row and "NO-HANDOFF" not in row
    assert "DIVERGED" not in row
    # Decode p99 grew past 1.2x under prefill load: the split failed.
    loaded["parsed"]["disagg"]["disagg"]["ratio"] = 1.4
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "ITL-REGRESSED" in bench_diff.ledger_row(a, c)
    # Zero entries over the wire: silently local prefill.
    loaded["parsed"]["disagg"]["disagg"]["ratio"] = 1.1
    loaded["parsed"]["disagg"]["disagg"]["handoff_entries"] = 0
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "NO-HANDOFF" in bench_diff.ledger_row(a, d)
    # Restored pages no longer replay the oracle.
    loaded["parsed"]["disagg"]["disagg"]["handoff_entries"] = 12
    loaded["parsed"]["disagg"]["disagg"]["tokens_match"] = False
    (tmp_path / "e.json").write_text(json.dumps(loaded))
    e = bench_diff.load_record(str(tmp_path / "e.json"))
    assert "DIVERGED" in bench_diff.ledger_row(a, e)
    # A skipped phase rides in parsed untouched, never in the row.
    loaded["parsed"]["disagg"] = {"skipped": "prompt too short"}
    (tmp_path / "f.json").write_text(json.dumps(loaded))
    f = bench_diff.load_record(str(tmp_path / "f.json"))
    assert "disagg_ratio" not in f
    assert "disagg decode p99" not in bench_diff.ledger_row(a, f)


def test_bench_diff_parses_autoscale_block(tmp_path):
    """Records grew an AUTOSCALE block (ISSUE 19, benchmark.py
    _run_autoscale_phase): the closed-loop controller's replica-minute
    bill vs the static peak fleet's, TTFT p99, and SLO-violation
    seconds over the deterministic diurnal+flash sim must surface in
    the normalized record, the field diff, and the ledger row — and
    the row must scream REPLICA-MINUTES-REGRESSED when the elastic
    bill reaches the static one (the autoscaler stopped paying for
    itself) and AUTOSCALE-SLO-VIOLATED when the controller fleet
    logged violation seconds (saving replica-minutes by burning user
    latency)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    )
    bench_diff = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_diff)

    base = {
        "n": 8,
        "rc": 0,
        "parsed": {"metric": "serving_tokens_per_sec", "value": 100.0,
                   "unit": "tokens/sec", "platform": "tpu"},
    }
    loaded = json.loads(json.dumps(base))
    loaded["n"] = 9
    loaded["parsed"]["autoscale"] = {
        "sim_seconds": 600, "slo_ms": 2500.0,
        "controller": {
            "replica_minutes": 23.3, "ttft_p99_ms": 498.8,
            "slo_violations": 0, "peak_replicas": 5,
            "scale_ups": 7, "scale_downs": 6, "role_flips": 0,
            "actions": 13,
        },
        "static_peak": {
            "replicas": 4, "replica_minutes": 40.0,
            "ttft_p99_ms": 349.8, "slo_violations": 0,
        },
        "replica_minutes_saved": 0.417,
    }
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(loaded))
    a = bench_diff.load_record(str(tmp_path / "a.json"))
    b = bench_diff.load_record(str(tmp_path / "b.json"))
    assert b["autoscale_replica_minutes"] == 23.3
    assert b["autoscale_static_minutes"] == 40.0
    assert b["autoscale_violations"] == 0
    assert b["autoscale_minutes_saved"] == 0.417
    assert b["autoscale_actions"] == 13
    diff = "\n".join(bench_diff.diff_lines(a, b))
    assert "autoscale_replica_minutes" in diff
    assert "autoscale_violations" in diff
    row = bench_diff.ledger_row(a, b)
    assert "autoscale 23.3 vs static 40.0 replica-min" in row
    assert "13 actions" in row
    assert "REPLICA-MINUTES-REGRESSED" not in row
    assert "AUTOSCALE-SLO-VIOLATED" not in row
    # The elastic bill caught up with static peak: not paying for
    # itself anymore.
    loaded["parsed"]["autoscale"]["controller"]["replica_minutes"] = 41.0
    (tmp_path / "c.json").write_text(json.dumps(loaded))
    c = bench_diff.load_record(str(tmp_path / "c.json"))
    assert "REPLICA-MINUTES-REGRESSED" in bench_diff.ledger_row(a, c)
    # Violation seconds appeared: the savings are fake.
    loaded["parsed"]["autoscale"]["controller"]["replica_minutes"] = 23.3
    loaded["parsed"]["autoscale"]["controller"]["slo_violations"] = 4
    (tmp_path / "d.json").write_text(json.dumps(loaded))
    d = bench_diff.load_record(str(tmp_path / "d.json"))
    assert "AUTOSCALE-SLO-VIOLATED" in bench_diff.ledger_row(a, d)
    # A record without the block stays quiet in the row.
    assert "autoscale" not in bench_diff.ledger_row(a, a)
