"""From a profiler trace (``.xplane.pb``) to numbers: the window the trace
covers, the device's busy time in it, the operations and programs that took
the time, and the idle gaps.

A TPU's plane ``/device:TPU:<n>`` has several lines whose events overlap
(``XLA Modules`` holds each program, ``XLA Ops`` each operation inside it,
``Steps`` a third view).  Busy time is the union of the intervals of ONE
line, the operations', cut to the window; never a sum over lines or
devices.  With several devices it is the mean over them, and the breakdown
is the busiest device's.  On the CPU (rehearsals only) the XLA client's
threads on ``/host:CPU`` stand in for the device.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_left
from collections import Counter, defaultdict

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under the directory the server named."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every event with a duration."""
    return [
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for e in line.events if e.duration_ns > 0
    ]


def device_lines(profile) -> list[dict]:
    """One entry per device: its operation events and its program events."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            out.append({
                "device": plane.name,
                "ops": _events(lines[OPS_LINE]),
                "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            })
    if out:
        return out
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            ops = []
            for ln in plane.lines:
                if ln.name.startswith("tf_XLAPjRtCpuClient") or ln.name.startswith("tf_XLAEigen"):
                    ops += [e for e in _events(ln) if not e[2].startswith("ThreadpoolListener")]
            # The host's own view of each jitted call stands in for the
            # device's program line.
            modules = [
                (s, e, "jit_" + n[len("PjitFunction("):-1])
                for ln in plane.lines for s, e, n in _events(ln) if n.startswith("PjitFunction(")
            ]
            if ops:
                out.append({"device": "/host:CPU", "ops": sorted(ops), "modules": modules})
    return out


def union_s(intervals: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Seconds covered by the union of [start, end) ns intervals, cut to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals between the union's pieces inside [lo, hi)."""
    out, at = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def host_activity(profile) -> list[tuple[float, float, str]]:
    """Host events (python threads' frames and TraceMe spans) with a
    duration, for naming what the host did during a device gap."""
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if ln.name.startswith("tf_XLA"):
                continue
            out += [ev for ev in _events(ln) if not ev[2].startswith("$profiler")]
    return out


def _gap_label(gap: tuple[float, float], host: list[tuple[float, float, str]]) -> str:
    """The host event that covers most of the gap (innermost on ties: the
    shortest such event), or ``host_idle``."""
    best, best_cover, best_len = "host_idle", 0.0, float("inf")
    for s, e, name in host:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        if cover > best_cover * 1.001 or (cover >= best_cover * 0.999 and e - s < best_len):
            best, best_cover, best_len = name, cover, e - s
    return best[:80]


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12``."""
    return name.split(" = ", 1)[0][:80]


def leaves(ops: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """The operations that enclose no other (a ``while`` holds its body's
    operations on the same line: counting both would count the time twice)."""
    ordered = sorted(ops)
    return [
        ev for ev, nxt in zip(ordered, ordered[1:] + [(float("inf"), 0.0, "")])
        if nxt[0] >= ev[1]
    ]


def steps_of(ops_inside: list[tuple[float, float, str]]) -> int:
    """How many steps a program event ran: a scanned program runs its
    body's operations once per step, so the repeat count that holds most of
    the event's device time is the number of steps."""
    count: Counter = Counter()
    seconds: dict[str, float] = defaultdict(float)
    for s, e, name in leaves(ops_inside):
        count[name] += 1
        seconds[name] += e - s
    by_repeat: dict[int, float] = defaultdict(float)
    for name, n in count.items():
        by_repeat[n] += seconds[name]
    return max(by_repeat, key=by_repeat.get) if by_repeat else 1


def reduce(profile, top: int = 10) -> dict | None:
    """``window_s``, ``busy_s`` (mean over devices), and for the busiest
    device its ``device_ops``, ``program_events`` and ``idle_gaps``.  None
    when no operation ran on any device in the trace."""
    devices = [d for d in device_lines(profile) if d["ops"]]
    if not devices:
        return None
    lo = min(s for d in devices for s, _, _ in d["ops"])
    hi = max(e for d in devices for _, e, _ in d["ops"])
    window_s = (hi - lo) / 1e9
    for d in devices:
        d["busy_s"] = union_s([(s, e) for s, e, _ in d["ops"]], lo, hi)
    busiest = max(devices, key=lambda d: d["busy_s"])
    by_op: dict[str, float] = defaultdict(float)
    for s, e, name in leaves(busiest["ops"]):
        by_op[short(name)] += (e - s) / 1e9
    ops_sorted = sorted(busiest["ops"])
    starts = [s for s, _, _ in ops_sorted]
    program_events = []
    for s, e, name in busiest["modules"]:
        inside = ops_sorted[bisect_left(starts, s):bisect_left(starts, e)]
        program_events.append([name.split("(")[0], (e - s) / 1e9, steps_of(inside)])
    host = [ev for ev in host_activity(profile) if ev[1] - ev[0] >= 2e5]
    by_gap: dict[str, float] = defaultdict(float)
    longest = sorted(
        gaps([(s, e) for s, e, _ in busiest["ops"]], lo, hi), key=lambda g: g[0] - g[1]
    )
    for g in longest[:50]:
        by_gap[_gap_label(g, host)] += (g[1] - g[0]) / 1e9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "devices": len(devices),
        "busiest": busiest["device"],
        "busiest_busy_s": busiest["busy_s"],
        "device_ops": rank(by_op),
        "op_seconds": dict(by_op),
        "program_events": program_events,
        "idle_gaps": rank(by_gap),
    }
