#!/usr/bin/env python3
"""Diff two benchmark records (``{"n", "rc", "parsed": {...}}`` files)
into a markdown row.

Comparing rounds by eyeballing two JSON blobs is how regressions slip.
This tool normalizes two records, prints a field-by-field diff, and
emits a markdown row.  (The BENCH_rNN.json captures and the
docs/perf-ledger.md table it was written for were deleted in PR 21; the
cell benchmark of ROADMAP Speed 1 and the driver's PERF_LEDGER.jsonl
take their place, and Design 8 decides what of this differ survives.)

    python tools/bench_diff.py old.json new.json
    python tools/bench_diff.py --row-only old.json new.json

A record whose ``parsed`` is null (the bench crashed before printing its
JSON line) renders as "failed"; the row still carries the rc and error
tail so the reader sees WHY there is no number.
"""

from __future__ import annotations

import argparse
import json
import sys


def load_record(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    parsed = raw.get("parsed") or None
    rec = {
        "path": path,
        "round": raw.get("n"),
        "rc": raw.get("rc"),
        "parsed": parsed,
    }
    if parsed:
        rec.update(
            metric=parsed.get("metric"),
            value=parsed.get("value"),
            unit=parsed.get("unit"),
            vs_baseline=parsed.get("vs_baseline"),
            baseline=parsed.get("baseline"),
            platform=parsed.get("platform"),
            error=parsed.get("error"),
        )
        # Serving records carry the overlapped-pipeline block: the
        # discard count is the regression tell (a round whose discards
        # jump while throughput sags means the pipeline stopped staying
        # primed — exactly what a diff row should surface).
        overlap = parsed.get("overlap")
        if isinstance(overlap, dict):
            rec["overlap_discards"] = overlap.get("discards")
            rec["overlap_speedup"] = overlap.get("speedup")
        # KV cache tiering block (serving records): hit/restore/evict
        # counters plus the restore-vs-recompute speedup.  A round whose
        # hits collapse or whose recomputed resumes reappear means the
        # tiers stopped carrying the repeated-prefix/preemption load.
        # Anything else in `parsed` (e.g. daemon-side attribution
        # series, which live on the plugin's /metrics and have no
        # business in a BENCH record) is deliberately NOT normalized:
        # unknown blocks ride in rec["parsed"] untouched and never
        # reach diff_lines/ledger_row, so new telemetry cannot break
        # the ledger schema (pinned by tests/test_bench.py).
        # Tensor-parallel block (MULTICHIP serving rows): decode tokens/s
        # at tp=N vs tp=1, the scaling efficiency, and discards under tp.
        # An efficiency collapse (or tokens_match flipping false) between
        # rounds is the regression tell for the sharded engine path.
        tp = parsed.get("tp")
        if isinstance(tp, dict):
            rec["tp_size"] = tp.get("size")
            rec["tp_tokens_per_sec"] = tp.get("tokens_per_sec")
            rec["tp_speedup"] = tp.get("speedup")
            rec["tp_scaling_efficiency"] = tp.get("scaling_efficiency")
            rec["tp_discards"] = tp.get("discards")
            rec["tp_tokens_match"] = tp.get("tokens_match")
        # Chaos block (tools/chaos_report.py chaos_summary): scenario
        # counts plus the WORST per-class detector precision/recall of
        # the run.  A precision/recall sag (or slo_pass flipping false)
        # between rounds means a detector regressed against injected
        # ground truth — the chaos analogue of a throughput collapse.
        chaos = parsed.get("chaos")
        if isinstance(chaos, dict):
            rec["chaos_scenarios"] = chaos.get("scenarios")
            rec["chaos_passed"] = chaos.get("passed")
            rec["chaos_faults"] = chaos.get("faults_injected")
            rec["chaos_precision"] = chaos.get("precision")
            rec["chaos_recall"] = chaos.get("recall")
            rec["chaos_slo_pass"] = chaos.get("slo_pass")
        # Router block (ROUTER serving rows): KV prefix-hit rate and
        # client-observed TTFT p99 under prefix-affinity routing vs the
        # random-placement control over the same seeded traffic.  The
        # affinity hit-rate collapsing toward the random control (or
        # dropped streams appearing) between rounds means the router
        # stopped keeping sessions on their warm replicas.
        router = parsed.get("router")
        if isinstance(router, dict):
            rec["router_replicas"] = router.get("replicas")
            affinity = router.get("affinity") or {}
            control = router.get("random") or {}
            rec["router_affinity_hit_rate"] = affinity.get("hit_rate")
            rec["router_affinity_ttft_p99_ms"] = affinity.get("ttft_p99_ms")
            rec["router_home_rate"] = affinity.get("home_rate")
            rec["router_random_hit_rate"] = control.get("hit_rate")
            rec["router_random_ttft_p99_ms"] = control.get("ttft_p99_ms")
            rec["router_dropped"] = (
                None
                if affinity.get("dropped") is None
                and control.get("dropped") is None
                else (affinity.get("dropped") or 0)
                + (control.get("dropped") or 0)
            )
        # Fabric block (FABRIC serving rows, benchmark.py
        # _run_fabric_phase): fleet-wide KV prefix hits/request and
        # client TTFT p99 with the content-addressed fabric on vs the
        # affinity-only control over the same shared-prefix traffic.
        # The regression tells: cross_peer_pulls dropping to 0 (the
        # any-peer pull path stopped moving pages and "fabric" is
        # silently affinity-only — NO-FABRIC-HITS), or the fabric TTFT
        # p99 exceeding 1.2x the control's (FABRIC-TTFT-REGRESSED:
        # locating and pulling costs more than the prefill it saves).
        fabric = parsed.get("fabric")
        if isinstance(fabric, dict) and not fabric.get("skipped"):
            on = fabric.get("fabric") or {}
            off = fabric.get("control") or {}
            rec["fabric_hit_rate"] = on.get("hit_rate")
            rec["fabric_ttft_p99_ms"] = on.get("ttft_p99_ms")
            rec["fabric_cross_peer_pulls"] = on.get("cross_peer_pulls")
            rec["fabric_control_hit_rate"] = off.get("hit_rate")
            rec["fabric_control_ttft_p99_ms"] = off.get("ttft_p99_ms")
            rec["fabric_dropped"] = (
                None
                if on.get("dropped") is None and off.get("dropped") is None
                else (on.get("dropped") or 0) + (off.get("dropped") or 0)
            )
        # Overload block (OVERLOAD serving rows, benchmark.py
        # _run_overload_phase): high-priority TTFT p99 under a 2x
        # mixed-priority storm vs unloaded, the goodput ratio
        # (in-deadline tokens / all tokens), and the shed ledger.  The
        # regression tells: hi_ttft_ratio creeping past 1.2 (priority
        # admission stopped protecting the high class), goodput sagging,
        # or pool_exact flipping false (a shed leaked pages) — the row
        # screams on all three.
        overload = parsed.get("overload")
        if isinstance(overload, dict):
            rec["overload_goodput_ratio"] = overload.get("goodput_ratio")
            rec["overload_sheds"] = overload.get("sheds")
            rec["overload_hi_ttft_ratio"] = overload.get("hi_ttft_p99_ratio")
            rec["overload_hi_ttft_storm_ms"] = overload.get(
                "hi_ttft_p99_storm_ms"
            )
            rec["overload_pool_exact"] = overload.get("pool_exact")
        # Restart block (RESTART serving rows, benchmark.py
        # _run_restart_phase): cold vs warm post-restart TTFT p99
        # through the KV-arena snapshot, plus how many pages the warm
        # path actually restored.  The regression tells: restored pages
        # dropping to 0 (the snapshot stopped rehydrating) or the warm
        # p99 exceeding the cold one (speedup < 1 — the row screams
        # COLD-REGRESSED, because a restore path slower than a cold
        # start is worse than not having one).
        restart = parsed.get("restart")
        if isinstance(restart, dict) and not restart.get("skipped"):
            rec["restart_cold_ttft_p99_ms"] = (restart.get("cold") or {}).get(
                "ttft_p99_ms"
            )
            rec["restart_warm_ttft_p99_ms"] = (restart.get("warm") or {}).get(
                "ttft_p99_ms"
            )
            rec["restart_restored_pages"] = (restart.get("warm") or {}).get(
                "restored_pages"
            )
            rec["restart_warm_speedup"] = restart.get("warm_speedup")
        # Elastic block (ELASTIC serving rows, benchmark.py
        # _run_elastic_phase): cold-join vs peer-warmed-join TTFT p99
        # over shared-prefix sessions, through the GET /debug/snapshot
        # wire stream.  The regression tells: entries_restored dropping
        # to 0 (the peer transfer stopped rehydrating) or the warmed
        # join running SLOWER than a cold one (warmed_speedup < 1 — the
        # row screams NO-WARMUP, because a warm-up path that loses to a
        # cold start is worse than not having one).
        elastic = parsed.get("elastic")
        if isinstance(elastic, dict) and not elastic.get("skipped"):
            rec["elastic_cold_ttft_p99_ms"] = (
                elastic.get("cold_join") or {}
            ).get("ttft_p99_ms")
            rec["elastic_warmed_ttft_p99_ms"] = (
                elastic.get("warmed_join") or {}
            ).get("ttft_p99_ms")
            rec["elastic_entries_restored"] = elastic.get("entries_restored")
            rec["elastic_wire_bytes"] = elastic.get("wire_bytes")
            rec["elastic_warmed_speedup"] = elastic.get("warmed_speedup")
        # Disagg block (DISAGG serving rows, benchmark.py
        # _run_disagg_phase): decode ITL p99 unloaded vs under
        # concurrent long-prompt prefill load, unified engine vs the
        # role-split prefill/decode pair moving KV over the handoff
        # wire.  The regression tells: the disagg loaded/unloaded ratio
        # creeping past 1.2x (the split stopped isolating decode from
        # prefill — ITL-REGRESSED), zero transferred entries
        # (NO-HANDOFF: the wire stopped moving pages and "disagg" is
        # silently local prefill), or tokens_match flipping false
        # (DIVERGED: restored pages no longer replay the local-prefill
        # oracle).
        disagg = parsed.get("disagg")
        if isinstance(disagg, dict) and not disagg.get("skipped"):
            rec["disagg_itl_p99_unloaded_ms"] = disagg.get(
                "itl_p99_unloaded_ms"
            )
            rec["disagg_unified_loaded_ms"] = (
                disagg.get("unified") or {}
            ).get("itl_p99_loaded_ms")
            rec["disagg_unified_ratio"] = (disagg.get("unified") or {}).get(
                "ratio"
            )
            rec["disagg_loaded_ms"] = (disagg.get("disagg") or {}).get(
                "itl_p99_loaded_ms"
            )
            rec["disagg_ratio"] = (disagg.get("disagg") or {}).get("ratio")
            rec["disagg_handoff_entries"] = (
                disagg.get("disagg") or {}
            ).get("handoff_entries")
            rec["disagg_tokens_match"] = (disagg.get("disagg") or {}).get(
                "tokens_match"
            )
        # Trace block (TRACE serving rows, benchmark.py's tracing
        # phase): measured spans-on vs spans-off per-token overhead
        # over the same jobs.  The regression tell: overhead creeping
        # past ~2% — the always-on span layer stopped being free and
        # the row screams TRACE-OVERHEAD.
        trace = parsed.get("trace")
        if isinstance(trace, dict):
            rec["trace_overhead"] = trace.get("overhead")
            rec["trace_spans"] = trace.get("spans_recorded")
        # Kernels block (KERNELS serving rows, benchmark.py
        # _run_kernels_phase): per-shape split-K-kernel-vs-gather
        # ratios plus the fused int8-vs-bf16 decode ratio.  The
        # regression tells: any shape's ratio sagging more than 10%
        # below its previously recorded value (KERNEL-REGRESSED names
        # the shapes), or the minimum ratio dropping below 1.0 — a
        # kernel slower than its own fallback (KERNEL-SLOWER-THAN-
        # GATHER) is the exact state the old single-pass ledger rows
        # were stuck in.
        kernels = parsed.get("kernels")
        if isinstance(kernels, dict):
            rec["kernels_min_ratio"] = kernels.get("min_kernel_vs_gather")
            rec["kernels_int8_vs_bf16"] = kernels.get("int8_vs_bf16")
            rec["kernels_shapes"] = {
                name: (shape or {}).get("kernel_vs_gather")
                for name, shape in (kernels.get("shapes") or {}).items()
            }
        # SLO block (SLO serving rows, benchmark.py _run_slo_phase):
        # measured slo-on vs slo-off per-token accounting overhead over
        # the same jobs, plus the alert-pipeline self-check.  The
        # regression tells: overhead creeping past 1% (the verdict/
        # usage seam stopped being free — SLO-OVERHEAD), or
        # burn_alert_fired flipping false (a synthetic sustained burn
        # no longer fires the fast-burn page rule — BURN-ALERT-MISSED,
        # the worst possible observability regression: the pager is
        # dead and nothing else would say so).
        slo = parsed.get("slo")
        if isinstance(slo, dict):
            rec["slo_overhead"] = slo.get("overhead")
            rec["slo_verdicts"] = slo.get("sli_verdicts")
            rec["slo_burn_alert_fired"] = slo.get("burn_alert_fired")
        # Canary block (CANARY serving rows, benchmark.py
        # _run_canary_phase): measured prober-on vs prober-off serving
        # throughput overhead, plus the injected-corruption self-check
        # (a probe stream with one flipped token MUST verdict
        # mismatch).  The regression tells: overhead creeping past 1%
        # (active probing stopped being free — PROBE-OVERHEAD), or
        # mismatch_detected flipping false (MISMATCH-MISSED, the worst
        # possible correctness-plane regression: the detector is blind
        # and nothing else would say so).
        canary = parsed.get("canary")
        if isinstance(canary, dict):
            rec["canary_overhead"] = canary.get("overhead")
            rec["canary_probes"] = canary.get("probes")
            rec["canary_mismatch_detected"] = canary.get(
                "mismatch_detected"
            )
            rec["canary_fences"] = canary.get("fences")
        # Postmortem block (POSTMORTEM serving rows, benchmark.py
        # _run_postmortem_phase): measured collector-armed vs
        # collector-off serving throughput overhead, plus the
        # archaeology self-check (an injected watchdog-source fence
        # incident MUST land one fleet bundle that classifies as
        # watchdog_hang from disk).  The regression tells: overhead
        # creeping past 1% (incident capture stopped being free —
        # CAPTURE-OVERHEAD), bundle_found flipping false
        # (CAPTURE-MISSED: the black box records nothing exactly when
        # it matters), or rootcause_ok flipping false (ROOTCAUSE-WRONG:
        # the classifier points operators at the wrong subsystem, worse
        # than no verdict).
        postmortem = parsed.get("postmortem")
        if isinstance(postmortem, dict):
            rec["postmortem_overhead"] = postmortem.get("overhead")
            rec["postmortem_captures"] = postmortem.get("captures")
            rec["postmortem_bundle_found"] = postmortem.get(
                "bundle_found"
            )
            rec["postmortem_root_cause"] = postmortem.get("root_cause")
            rec["postmortem_rootcause_ok"] = postmortem.get(
                "rootcause_ok"
            )
        # Autoscale block (AUTOSCALE serving rows, benchmark.py
        # _run_autoscale_phase): the closed-loop fleet controller vs a
        # static peak-provisioned fleet over the same deterministic
        # diurnal+flash demand trace.  The regression tells: the
        # controller's replica-minute bill reaching the static fleet's
        # (REPLICA-MINUTES-REGRESSED: the autoscaler stopped paying for
        # itself — a fleet that costs as much as static peak with none
        # of its simplicity should not exist), or controller SLO
        # violation seconds appearing (AUTOSCALE-SLO-VIOLATED: it
        # "saves" replica-minutes by burning user latency).
        autoscale = parsed.get("autoscale")
        if isinstance(autoscale, dict):
            ctrl = autoscale.get("controller") or {}
            static = autoscale.get("static_peak") or {}
            rec["autoscale_replica_minutes"] = ctrl.get("replica_minutes")
            rec["autoscale_ttft_p99_ms"] = ctrl.get("ttft_p99_ms")
            rec["autoscale_violations"] = ctrl.get("slo_violations")
            rec["autoscale_actions"] = ctrl.get("actions")
            rec["autoscale_static_minutes"] = static.get(
                "replica_minutes"
            )
            rec["autoscale_static_ttft_p99_ms"] = static.get(
                "ttft_p99_ms"
            )
            rec["autoscale_minutes_saved"] = autoscale.get(
                "replica_minutes_saved"
            )
        kvcache = parsed.get("kvcache")
        if isinstance(kvcache, dict):
            rec["kvcache_hits"] = kvcache.get("hits")
            rec["kvcache_restores"] = kvcache.get("restores")
            rec["kvcache_reclaims"] = kvcache.get("reclaims")
            rec["kvcache_restore_speedup"] = kvcache.get("restore_speedup")
            rec["kvcache_resumes_restored"] = kvcache.get("resumes_restored")
            rec["kvcache_resumes_recomputed"] = kvcache.get(
                "resumes_recomputed"
            )
    return rec


# A shape "regresses past its recorded ratio" when the new record's
# kernel-vs-gather falls more than this fraction below the old one
# (timing jitter on min-of-N CPU smoke is a few percent; 10% is signal).
KERNEL_REGRESS_TOLERANCE = 0.9


def kernel_regressions(a: dict, b: dict) -> list[str]:
    """Shapes present in BOTH records whose kernel-vs-gather ratio fell
    past the recorded value (beyond tolerance), sorted for stable rows."""
    old = a.get("kernels_shapes") or {}
    new = b.get("kernels_shapes") or {}
    out = []
    for name in sorted(set(old) & set(new)):
        va, vb = old[name], new[name]
        if va and vb and vb < va * KERNEL_REGRESS_TOLERANCE:
            out.append(name)
    return out


def _fmt_value(rec: dict) -> str:
    if not rec["parsed"]:
        return f"failed (rc {rec['rc']})"
    return f"{rec['value']} ({rec['platform']})"


def diff_lines(a: dict, b: dict) -> list[str]:
    lines = [f"BENCH r{a['round']:02d} -> r{b['round']:02d}"]
    for field in (
        "metric", "value", "unit", "vs_baseline", "platform", "rc", "error",
        "overlap_speedup", "overlap_discards",
        "tp_size", "tp_tokens_per_sec", "tp_speedup",
        "tp_scaling_efficiency", "tp_discards", "tp_tokens_match",
        "kernels_min_ratio", "kernels_int8_vs_bf16",
        "kvcache_hits", "kvcache_restores", "kvcache_reclaims",
        "kvcache_restore_speedup", "kvcache_resumes_restored",
        "kvcache_resumes_recomputed",
        "chaos_scenarios", "chaos_passed", "chaos_faults",
        "chaos_precision", "chaos_recall", "chaos_slo_pass",
        "overload_goodput_ratio", "overload_sheds",
        "overload_hi_ttft_ratio", "overload_hi_ttft_storm_ms",
        "overload_pool_exact",
        "restart_cold_ttft_p99_ms", "restart_warm_ttft_p99_ms",
        "restart_restored_pages", "restart_warm_speedup",
        "elastic_cold_ttft_p99_ms", "elastic_warmed_ttft_p99_ms",
        "elastic_entries_restored", "elastic_wire_bytes",
        "elastic_warmed_speedup",
        "disagg_itl_p99_unloaded_ms", "disagg_unified_loaded_ms",
        "disagg_unified_ratio", "disagg_loaded_ms", "disagg_ratio",
        "disagg_handoff_entries", "disagg_tokens_match",
        "trace_overhead", "trace_spans",
        "slo_overhead", "slo_verdicts", "slo_burn_alert_fired",
        "canary_overhead", "canary_probes", "canary_mismatch_detected",
        "canary_fences",
        "postmortem_overhead", "postmortem_captures",
        "postmortem_bundle_found", "postmortem_root_cause",
        "postmortem_rootcause_ok",
        "autoscale_replica_minutes", "autoscale_static_minutes",
        "autoscale_minutes_saved", "autoscale_ttft_p99_ms",
        "autoscale_static_ttft_p99_ms", "autoscale_violations",
        "autoscale_actions",
        "router_replicas", "router_affinity_hit_rate",
        "router_affinity_ttft_p99_ms", "router_home_rate",
        "router_random_hit_rate", "router_random_ttft_p99_ms",
        "router_dropped",
        "fabric_hit_rate", "fabric_ttft_p99_ms",
        "fabric_cross_peer_pulls", "fabric_control_hit_rate",
        "fabric_control_ttft_p99_ms", "fabric_dropped",
    ):
        va, vb = a.get(field), b.get(field)
        if va is None and vb is None:
            continue
        marker = " " if va == vb else "*"
        lines.append(f"  {marker} {field}: {va!r} -> {vb!r}")
    # Per-shape kernel ratios: one line per shape in either record, with
    # the same changed-marker convention.
    shapes_a = a.get("kernels_shapes") or {}
    shapes_b = b.get("kernels_shapes") or {}
    for name in sorted(set(shapes_a) | set(shapes_b)):
        va, vb = shapes_a.get(name), shapes_b.get(name)
        marker = " " if va == vb else "*"
        lines.append(f"  {marker} kernels[{name}]: {va!r} -> {vb!r}")
    for name in kernel_regressions(a, b):
        lines.append(
            f"  ! KERNEL-REGRESSED {name}: {shapes_a[name]!r} -> "
            f"{shapes_b[name]!r} (past the {KERNEL_REGRESS_TOLERANCE:.0%} "
            "tolerance of its recorded ratio)"
        )
    if (
        isinstance(a.get("value"), (int, float))
        and isinstance(b.get("value"), (int, float))
        and a["value"]
    ):
        ratio = b["value"] / a["value"]
        lines.append(f"    value ratio: {ratio:.3f}x")
    return lines


def ledger_row(a: dict, b: dict) -> str:
    metric = b.get("metric") or a.get("metric") or "?"
    measured = f"{_fmt_value(a)} → {_fmt_value(b)}"
    status = "both failed"
    if b["parsed"]:
        status = (
            f"platform {b.get('platform')}"
            + (f"; note: {b['error']}" if b.get("error") else "")
            + (
                f"; overlap discards {b['overlap_discards']}"
                if b.get("overlap_discards") is not None
                else ""
            )
            + (
                f"; tp={b['tp_size']} {b.get('tp_tokens_per_sec')} tok/s "
                f"(eff {b.get('tp_scaling_efficiency')}, discards "
                f"{b.get('tp_discards')}"
                + ("" if b.get("tp_tokens_match", True) else ", DIVERGED")
                + ")"
                if b.get("tp_size") is not None
                else ""
            )
            + (
                f"; kvcache hits {b['kvcache_hits']} "
                f"restore {b.get('kvcache_restore_speedup')}x "
                f"resumes {b.get('kvcache_resumes_restored')}r/"
                f"{b.get('kvcache_resumes_recomputed')}c"
                if b.get("kvcache_hits") is not None
                else ""
            )
            + (
                f"; router K={b['router_replicas']} affinity "
                f"{b.get('router_affinity_hit_rate')} hits/req "
                f"p99 {b.get('router_affinity_ttft_p99_ms')}ms vs random "
                f"{b.get('router_random_hit_rate')} / "
                f"{b.get('router_random_ttft_p99_ms')}ms"
                + (
                    f", DROPPED {b['router_dropped']}"
                    if b.get("router_dropped")
                    else ""
                )
                if b.get("router_replicas") is not None
                else ""
            )
            + (
                f"; fabric {b['fabric_hit_rate']} hits/req "
                f"p99 {b.get('fabric_ttft_p99_ms')}ms "
                f"({b.get('fabric_cross_peer_pulls')} pulls) vs control "
                f"{b.get('fabric_control_hit_rate')} / "
                f"{b.get('fabric_control_ttft_p99_ms')}ms"
                + (
                    ", NO-FABRIC-HITS"
                    if b.get("fabric_cross_peer_pulls") == 0
                    else ""
                )
                + (
                    ", FABRIC-TTFT-REGRESSED"
                    if (b.get("fabric_ttft_p99_ms") or 0.0)
                    > 1.2 * (b.get("fabric_control_ttft_p99_ms") or float("inf"))
                    else ""
                )
                + (
                    f", DROPPED {b['fabric_dropped']}"
                    if b.get("fabric_dropped")
                    else ""
                )
                if b.get("fabric_hit_rate") is not None
                else ""
            )
            + (
                f"; kernels min {b['kernels_min_ratio']}x vs gather "
                f"(int8/bf16 {b.get('kernels_int8_vs_bf16')}x"
                + (
                    ", KERNEL-SLOWER-THAN-GATHER"
                    if (b.get("kernels_min_ratio") or 1.0) < 1.0
                    else ""
                )
                + (
                    ", KERNEL-REGRESSED("
                    + ",".join(kernel_regressions(a, b))
                    + ")"
                    if kernel_regressions(a, b)
                    else ""
                )
                + ")"
                if b.get("kernels_min_ratio") is not None
                else ""
            )
            + (
                f"; chaos {b['chaos_passed']}/{b['chaos_scenarios']} "
                f"(p {b.get('chaos_precision')}, r {b.get('chaos_recall')}"
                + ("" if b.get("chaos_slo_pass", True) else ", SLO-FAIL")
                + ")"
                if b.get("chaos_scenarios") is not None
                else ""
            )
            + (
                f"; restart warm p99 {b['restart_warm_ttft_p99_ms']}ms "
                f"vs cold {b.get('restart_cold_ttft_p99_ms')}ms "
                f"({b.get('restart_restored_pages')} pages restored"
                + (
                    ", COLD-REGRESSED"
                    if (b.get("restart_warm_speedup") or 1.0) < 1.0
                    else ""
                )
                + (
                    ", NO-RESTORE"
                    if b.get("restart_restored_pages") == 0
                    else ""
                )
                + ")"
                if b.get("restart_warm_ttft_p99_ms") is not None
                else ""
            )
            + (
                f"; elastic warmed-join p99 "
                f"{b['elastic_warmed_ttft_p99_ms']}ms vs cold "
                f"{b.get('elastic_cold_ttft_p99_ms')}ms "
                f"({b.get('elastic_entries_restored')} entries shipped"
                + (
                    ", NO-WARMUP"
                    if (b.get("elastic_warmed_speedup") or 1.0) < 1.0
                    else ""
                )
                + (
                    ", NO-TRANSFER"
                    if b.get("elastic_entries_restored") == 0
                    else ""
                )
                + ")"
                if b.get("elastic_warmed_ttft_p99_ms") is not None
                else ""
            )
            + (
                f"; disagg decode p99 {b['disagg_loaded_ms']}ms under "
                f"prefill load ({b.get('disagg_ratio')}x of unloaded vs "
                f"unified {b.get('disagg_unified_ratio')}x, "
                f"{b.get('disagg_handoff_entries')} entries shipped"
                + (
                    ", ITL-REGRESSED"
                    if (b.get("disagg_ratio") or 0.0) > 1.2
                    else ""
                )
                + (
                    ", NO-HANDOFF"
                    if b.get("disagg_handoff_entries") == 0
                    else ""
                )
                + (
                    ""
                    if b.get("disagg_tokens_match", True)
                    else ", DIVERGED"
                )
                + ")"
                if b.get("disagg_loaded_ms") is not None
                else ""
            )
            + (
                f"; trace overhead {b['trace_overhead']} "
                f"({b.get('trace_spans')} spans"
                + (
                    ", TRACE-OVERHEAD"
                    if (b.get("trace_overhead") or 0.0) > 0.02
                    else ""
                )
                + ")"
                if b.get("trace_overhead") is not None
                else ""
            )
            + (
                f"; slo overhead {b['slo_overhead']} "
                f"({b.get('slo_verdicts')} verdicts"
                + (
                    ", SLO-OVERHEAD"
                    if (b.get("slo_overhead") or 0.0) > 0.01
                    else ""
                )
                + (
                    ""
                    if b.get("slo_burn_alert_fired", True)
                    else ", BURN-ALERT-MISSED"
                )
                + ")"
                if b.get("slo_overhead") is not None
                else ""
            )
            + (
                f"; canary overhead {b['canary_overhead']} "
                f"({b.get('canary_probes')} probes, "
                f"{b.get('canary_fences')} fences"
                + (
                    ", PROBE-OVERHEAD"
                    if (b.get("canary_overhead") or 0.0) > 0.01
                    else ""
                )
                + (
                    ""
                    if b.get("canary_mismatch_detected", True)
                    else ", MISMATCH-MISSED"
                )
                + ")"
                if b.get("canary_overhead") is not None
                else ""
            )
            + (
                f"; postmortem overhead {b['postmortem_overhead']} "
                f"({b.get('postmortem_captures')} bundles, "
                f"root {b.get('postmortem_root_cause')}"
                + (
                    ", CAPTURE-OVERHEAD"
                    if (b.get("postmortem_overhead") or 0.0) > 0.01
                    else ""
                )
                + (
                    ""
                    if b.get("postmortem_bundle_found", True)
                    else ", CAPTURE-MISSED"
                )
                + (
                    ""
                    if b.get("postmortem_rootcause_ok", True)
                    else ", ROOTCAUSE-WRONG"
                )
                + ")"
                if b.get("postmortem_overhead") is not None
                else ""
            )
            + (
                f"; autoscale {b['autoscale_replica_minutes']} vs "
                f"static {b.get('autoscale_static_minutes')} "
                f"replica-min ({b.get('autoscale_actions')} actions, "
                f"p99 {b.get('autoscale_ttft_p99_ms')}ms"
                + (
                    ", REPLICA-MINUTES-REGRESSED"
                    if (b.get("autoscale_replica_minutes") or 0.0)
                    >= (
                        b.get("autoscale_static_minutes")
                        or float("inf")
                    )
                    else ""
                )
                + (
                    ", AUTOSCALE-SLO-VIOLATED"
                    if (b.get("autoscale_violations") or 0) > 0
                    else ""
                )
                + ")"
                if b.get("autoscale_replica_minutes") is not None
                else ""
            )
            + (
                f"; overload goodput {b['overload_goodput_ratio']} "
                f"sheds {b.get('overload_sheds')} hi-p99 "
                f"{b.get('overload_hi_ttft_ratio')}x"
                + (
                    ", HI-TTFT-REGRESSED"
                    if (b.get("overload_hi_ttft_ratio") or 0) > 1.2
                    else ""
                )
                + (
                    ""
                    if b.get("overload_pool_exact", True)
                    else ", PAGE-LEAK"
                )
                if b.get("overload_goodput_ratio") is not None
                else ""
            )
        )
    return (
        f"| Driver BENCH headline r{a['round']:02d}→r{b['round']:02d} "
        f"({metric}) | {measured} | r{b['round']} | `tools/bench_diff.py "
        f"{a['path']} {b['path']}` | {status} |"
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bench-diff",
        description="diff two benchmark records; emit a markdown row",
    )
    p.add_argument("old", help="earlier record")
    p.add_argument("new", help="later record")
    p.add_argument(
        "--row-only",
        action="store_true",
        help="print only the markdown ledger row (for shell backfills)",
    )
    args = p.parse_args(argv)
    try:
        a, b = load_record(args.old), load_record(args.new)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench-diff: {e}", file=sys.stderr)
        return 1
    if not args.row_only:
        print("\n".join(diff_lines(a, b)), file=sys.stderr)
    print(ledger_row(a, b))
    return 0


if __name__ == "__main__":
    sys.exit(main())
