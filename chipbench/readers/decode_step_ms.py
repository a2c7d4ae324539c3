"""Device time of one decode step: each decode program event of the traced
window (``jit_block`` scans up to ``decode_block`` steps, ``jit_step`` one)
over the steps it ran, the median over the events."""

from statistics import median

from chipbench.readers._traced import decode_events


def read(ctx):
    events = decode_events(ctx)
    if not events:
        return None
    return median(d / n for d, n in events) * 1e3
