#!/usr/bin/env python3
"""Chaos scenario scoring + scenario-matrix report.

The chaos suite (tests/test_chaos_scenarios.py, `--slow`) injects
ground-truth faults into a simulated fleet / loaded serving engine and
collects what the stack's OWN detectors reported (flight events,
/debug/incidents records, metrics).  This tool owns the join:

- :func:`score_detections` matches detections to injected fault windows
  per fault class and computes **measured** precision/recall plus
  detection-latency quantiles — "we have detectors" becomes "we know
  what our detectors catch".
- :func:`render_matrix` renders a markdown scenario-matrix table
  (docs/chaos.md embeds one).
- :func:`chaos_summary` / :func:`ledger_row` fold a result set into
  one JSON `chaos` block and one markdown row.

Usage (scenario tests write one JSON result per scenario into
$TPU_CHAOS_RESULTS_DIR):

    TPU_CHAOS_RESULTS_DIR=/tmp/chaos python -m pytest \\
        tests/test_chaos_scenarios.py -m slow -q
    python tools/chaos_report.py /tmp/chaos            # matrix + row
    python tools/chaos_report.py --run                 # both steps

Scoring semantics (docs/chaos.md "Reading the report"):

- An injected fault is a window ``[t0, t1]``; a detection is a point
  ``ts``.  A detection MATCHES a fault when their class-specific keys
  agree (node/device, when present on both) and
  ``t0 <= ts <= t1 + grace``.
- **recall** = matched faults / injected faults (did we catch it?),
- **precision** = matched detections / all detections (when the
  detector speaks, is it right?).  Both are per fault class; a class
  with no detections scores precision 1.0 (vacuous) and recall 0.0.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# "replica" is the router-scenario key (a "host:port" name): an
# injected replica kill must only match detections naming THAT replica,
# so the clean replicas score the precision control.  "rid" is the
# overload-scenario key: an injected doomed request must only match a
# shed decision naming THAT request id, so every survivor is a
# precision control.
_MATCH_KEYS = ("node", "device", "drift", "replica", "rid")


def _matches(inj: dict, det: dict) -> bool:
    """Class-specific key agreement: any key present on BOTH records
    must agree (records may omit keys — a fleet-wide fault has no
    device)."""
    for key in _MATCH_KEYS:
        if key in inj and key in det and inj[key] != det[key]:
            return False
    return True


def _quantile(sorted_values: list[float], q: float) -> float | None:
    if not sorted_values:
        return None
    idx = min(len(sorted_values) - 1, int(q * len(sorted_values)))
    return sorted_values[idx]


def score_detections(
    injected: list[dict],
    detected: list[dict],
    grace_s: float = 5.0,
) -> dict:
    """Join detections against injected fault windows; returns per-class
    tp/fp/fn, precision, recall, and detection-latency quantiles.

    injected: [{"cls", "t0", "t1", ...match keys}]
    detected: [{"cls", "ts",        ...match keys}]
    """
    classes = sorted(
        {f["cls"] for f in injected} | {d["cls"] for d in detected}
    )
    per_class: dict[str, dict] = {}
    for cls in classes:
        inj = sorted(
            (f for f in injected if f["cls"] == cls), key=lambda f: f["t0"]
        )
        det = sorted(
            (d for d in detected if d["cls"] == cls), key=lambda d: d["ts"]
        )
        matched_det: set[int] = set()
        latencies: list[float] = []
        tp = 0
        for fault in inj:
            # Each fault claims the EARLIEST unmatched detection in its
            # window — one per fault, so back-to-back faults with
            # overlapping windows (a restart storm) each keep their own
            # detection instead of the first fault swallowing them all.
            for i, d in enumerate(det):
                if i in matched_det:
                    continue
                if not _matches(fault, d):
                    continue
                if fault["t0"] <= d["ts"] <= fault["t1"] + grace_s:
                    matched_det.add(i)
                    tp += 1
                    latencies.append(d["ts"] - fault["t0"])
                    break
        # Detections matching ANY fault window (even an already-matched
        # one) are not false positives: one fault may legitimately fire
        # several reports (cooldown re-fires, per-chip fan-out).
        fp = 0
        for i, d in enumerate(det):
            if i in matched_det:
                continue
            if any(
                _matches(f, d) and f["t0"] <= d["ts"] <= f["t1"] + grace_s
                for f in inj
            ):
                continue
            fp += 1
        fn = len(inj) - tp
        true_det = len(det) - fp
        latencies.sort()
        per_class[cls] = {
            "injected": len(inj),
            "detections": len(det),
            "tp": tp,
            "fp": fp,
            "fn": fn,
            "precision": (true_det / len(det)) if det else 1.0,
            "recall": (tp / len(inj)) if inj else 1.0,
            "latency_p50_s": _quantile(latencies, 0.50),
            "latency_max_s": latencies[-1] if latencies else None,
        }
    overall = {
        "injected": sum(c["injected"] for c in per_class.values()),
        "tp": sum(c["tp"] for c in per_class.values()),
        "fp": sum(c["fp"] for c in per_class.values()),
        "fn": sum(c["fn"] for c in per_class.values()),
        "precision": (
            min(c["precision"] for c in per_class.values())
            if per_class
            else 1.0
        ),
        "recall": (
            min(c["recall"] for c in per_class.values()) if per_class else 1.0
        ),
    }
    return {"per_class": per_class, "overall": overall, "grace_s": grace_s}


# ------------------------------------------------------------------ report


def _fmt(value, digits: int = 2) -> str:
    if value is None:
        return "—"
    if isinstance(value, bool):
        return "yes" if value else "NO"
    if isinstance(value, float):
        return f"{value:.{digits}f}"
    return str(value)


def render_matrix(results: list[dict]) -> str:
    """Markdown scenario matrix: one row per (scenario, fault class)
    with measured precision/recall/latency, plus the scenario's SLO
    verdict."""
    lines = [
        "| Scenario | Fault class | Injected | Precision | Recall "
        "| Detect p50 (s) | SLO | Pass |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for res in results:
        score = res.get("score", {})
        slo = res.get("slo", {})
        slo_cell = _fmt(slo.get("pass", None))
        per_class = score.get("per_class", {})
        if not per_class:
            lines.append(
                f"| {res['scenario']} | — | 0 | — | — | — | {slo_cell} "
                f"| {_fmt(res.get('pass'))} |"
            )
            continue
        for cls, c in sorted(per_class.items()):
            lines.append(
                f"| {res['scenario']} | {cls} | {c['injected']} "
                f"| {_fmt(c['precision'])} | {_fmt(c['recall'])} "
                f"| {_fmt(c['latency_p50_s'])} | {slo_cell} "
                f"| {_fmt(res.get('pass'))} |"
            )
    return "\n".join(lines)


def chaos_summary(results: list[dict]) -> dict:
    """The `chaos` JSON block of a run: scenario counts plus the WORST
    per-class precision/recall across the whole run — a single regressing
    detector must drag the headline number, not hide in an average."""
    precisions: list[float] = []
    recalls: list[float] = []
    injected = 0
    for res in results:
        for c in res.get("score", {}).get("per_class", {}).values():
            precisions.append(c["precision"])
            recalls.append(c["recall"])
            injected += c["injected"]
    return {
        "scenarios": len(results),
        "passed": sum(1 for r in results if r.get("pass")),
        "faults_injected": injected,
        "precision": round(min(precisions), 4) if precisions else None,
        "recall": round(min(recalls), 4) if recalls else None,
        "slo_pass": all(
            r.get("slo", {}).get("pass", True) for r in results
        ),
    }


def ledger_row(results: list[dict]) -> str:
    """One markdown row for the run."""
    s = chaos_summary(results)
    measured = (
        f"{s['passed']}/{s['scenarios']} scenarios, "
        f"{s['faults_injected']} faults, precision {_fmt(s['precision'])}, "
        f"recall {_fmt(s['recall'])}"
    )
    status = "SLO pass" if s["slo_pass"] else "SLO FAIL"
    return (
        f"| Chaos scenario matrix | {measured} | — | "
        f"`tools/chaos_report.py --run` | {status} |"
    )


# --------------------------------------------------------------------- CLI


def load_results(paths: list[str]) -> list[dict]:
    results = []
    for path in paths:
        with open(path) as f:
            record = json.load(f)
        # Only scenario records: the results dir may also hold this
        # tool's own --json summary or unrelated JSON.
        if record.get("scenario"):
            results.append(record)
    return sorted(results, key=lambda r: r["scenario"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chaos-report",
        description="score chaos scenario results; emit the matrix + "
        "ledger row",
    )
    p.add_argument(
        "results_dir",
        nargs="?",
        default=os.environ.get("TPU_CHAOS_RESULTS_DIR", ""),
        help="directory of tpu-chaos-scenario JSON results "
        "(default: $TPU_CHAOS_RESULTS_DIR)",
    )
    p.add_argument(
        "--run",
        action="store_true",
        help="run the --slow scenario suite first (pytest "
        "tests/test_chaos_scenarios.py -m slow), writing results into "
        "results_dir (a tempdir when unset)",
    )
    p.add_argument(
        "--json",
        default="",
        help="also write {'chaos': summary, 'results': [...]} JSON here",
    )
    args = p.parse_args(argv)
    results_dir = args.results_dir
    if args.run:
        if not results_dir:
            import tempfile

            results_dir = tempfile.mkdtemp(prefix="tpu-chaos-")
        env = dict(os.environ)
        env["TPU_CHAOS_RESULTS_DIR"] = results_dir
        env.setdefault("JAX_PLATFORMS", "cpu")
        rc = subprocess.call(
            [
                sys.executable, "-m", "pytest",
                os.path.join(REPO_ROOT, "tests", "test_chaos_scenarios.py"),
                "-m", "slow", "-q", "-p", "no:cacheprovider",
            ],
            env=env,
            cwd=REPO_ROOT,
        )
        if rc != 0:
            print(
                f"chaos-report: scenario suite exited {rc} (scoring "
                "whatever results it wrote)",
                file=sys.stderr,
            )
    if not results_dir:
        print(
            "chaos-report: no results dir (pass one, set "
            "$TPU_CHAOS_RESULTS_DIR, or use --run)",
            file=sys.stderr,
        )
        return 2
    paths = sorted(glob.glob(os.path.join(results_dir, "*.json")))
    if not paths:
        print(f"chaos-report: no results under {results_dir}", file=sys.stderr)
        return 2
    results = load_results(paths)
    print(render_matrix(results))
    print()
    print(ledger_row(results))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                {"chaos": chaos_summary(results), "results": results},
                f,
                indent=2,
            )
        print(f"wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
