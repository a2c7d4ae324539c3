"""The recurrence kernel's share of its roofline in the traced window: the
least time of every ``kda_decode`` call (the bytes it must move, the
family's ``kda_decode_bytes`` over the active slots, at the chip's peak
bandwidth) over those calls' device time.  The device time is the sum of
the operations named ``kda_decode`` on the busiest device's operation line
(``trace.py`` ``reduce``: ``op_seconds``); the calls are the traced decode
programs' steps times the linear-attention layers; the active slots the
mean of the ``tpu_engine_active_slots`` samples in the window, as
``slot_occupancy`` reads them.  Nothing to read where no such operation ran
(a program without the kernel, or off the TPU)."""

import sys

from chipbench import families, peaks
from chipbench.readers._traced import decode_events


def read(ctx):
    reduced = ctx.get("trace_reduced") or {}
    device_s = sum(s for name, s in (reduced.get("op_seconds") or {}).items() if "kda_decode" in name)
    m = ctx["cell"].config["model"]
    family = families.of(m)
    t0, seconds = ctx["window"]
    active = [s["tpu_engine_active_slots"] for t, s in ctx["scraped"]["samples"]
              if t0 <= t <= t0 + seconds and "tpu_engine_active_slots" in s]
    steps = sum(n for _, n in decode_events(ctx))
    if device_s <= 0 or not active or not steps or not hasattr(family, "kda_decode_bytes"):
        return None
    calls = steps * family.linear_layers(m)
    least = calls * family.kda_decode_bytes(m, sum(active) / len(active)) / peaks.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    print(f"kda_decode_roofline: {calls} calls, {device_s * 1e3:.2f} ms", file=sys.stderr)
    return 100.0 * least / device_s
