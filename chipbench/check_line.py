"""The contract of a run's last line, as code.  ``run.py`` calls
``problems`` on its own line before it prints it, in every run; so do the
tests.  An empty list means the line may be printed."""

from __future__ import annotations

import json
import math
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _reject_constant(token: str):
    raise ValueError(f"{token} is not JSON")


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def _count(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= 0


def problems(text: str, metrics: dict[str, str], chips: int, traced: bool, platform: str | None = None) -> list[str]:
    """What is wrong with ``text`` as the last line of a run of a cell whose
    mode reports ``metrics`` (name -> unit) on ``chips`` chips."""
    if "\n" in text.rstrip("\n"):
        return ["the line holds a newline"]
    try:
        line = json.loads(text, parse_constant=_reject_constant)
    except ValueError as e:
        return [f"not one JSON object: {e}"]
    if not isinstance(line, dict):
        return ["not a JSON object"]
    out = [f"key {k!r} is missing" for k in TOP_KEYS if k not in line]
    if out:
        return out
    if not isinstance(line["correct"], bool):
        out.append("correct is not a boolean")
    for k in ("attempted", "failed"):
        if not _count(line[k]):
            out.append(f"{k} is not a whole number >= 0")
    if _count(line["attempted"]) and _count(line["failed"]) and line["failed"] > line["attempted"]:
        out.append("failed exceeds attempted")
    got = line["metrics"]
    if not isinstance(got, dict):
        return out + ["metrics is not an object"]
    for name, unit in metrics.items():
        if name not in got:
            out.append(f"metric {name!r} of this cell is missing")
    for name, m in got.items():
        if not NAME.match(name):
            out.append(f"metric name {name!r} uses characters outside the contract")
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            out.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _number(m["value"]):
            out.append(f"metric {name!r} has no finite number as its value: {m['value']!r}")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            out.append(f"metric {name!r} has a unit outside the contract: {m['unit']!r}")
        if name in metrics and m["unit"] != metrics[name]:
            out.append(f"metric {name!r} has unit {m['unit']!r}, the benchmark says {metrics[name]!r}")
        if name not in metrics:
            out.append(f"metric {name!r} is not one this cell reports in this mode")
        share = name.endswith("_roofline") or "mfu" in re.split(r"[._\-]", name)
        if share and _number(m["value"]) and not 0 < m["value"] <= 105:
            out.append(f"share {name!r} = {m['value']} is outside (0, 105]")
    dev = line["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for k in ("platform", "kind"):
        if not isinstance(dev.get(k), str) or not dev.get(k):
            out.append(f"device.{k} is missing")
    if platform is not None and dev.get("platform") != platform:
        out.append(f"device.platform is {dev.get('platform')!r}, the run was given {platform!r}")
    if dev.get("count") != chips or isinstance(dev.get("count"), bool):
        out.append(f"device.count is {dev.get('count')!r}, the cell asks for {chips}")
    peak = dev.get("memory_peak_bytes")
    if not _count(peak) or (dev.get("platform") == "tpu" and peak == 0):
        out.append(f"device.memory_peak_bytes is {peak!r}")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _number(busy) or not _number(window):
            out.append(f"traced run without busy_s and window_s: {busy!r}, {window!r}")
        elif not 0 < busy <= window:
            out.append(f"busy_s {busy} is not in (0, window_s {window}]")
        bd = line.get("breakdown")
        if bd is not None:
            if not isinstance(bd, dict):
                out.append("breakdown is not an object")
            else:
                for k in ("device_ops", "idle_gaps"):
                    rows = bd.get(k)
                    ok = isinstance(rows, list) and len(rows) <= 10 and all(
                        isinstance(r, list) and len(r) == 2 and isinstance(r[0], str) and _number(r[1])
                        for r in rows
                    )
                    if not ok:
                        out.append(f"breakdown.{k} is not a list of at most 10 [name, seconds]")
    elif "breakdown" in line or "busy_s" in dev:
        out.append("an untraced run carries trace readings")
    keys = list(line)
    if "compared" in line and keys[-1] != "compared":
        out.append("compared is not the last key")
    return out
