"""Shared by the readers of the owner loop's phase counters
(``tpu_engine_loop_<phase>_seconds_total``, models/engine_profiler.py): the
difference of a counter over the window, and the seconds in all step phases.
A program without the counters (an older commit) reads 0 everywhere: a
traced line has to hold every metric of its cell."""

STEP_PHASES = ("schedule", "prefill", "dispatch", "readback", "sample", "host_gap", "spec_verify")


def delta(ctx, name):
    """What the counter ``name`` grew by between the two scrapes."""
    a, b = ctx["scraped"]["before"], ctx["scraped"]["after"]
    return b.get(name, 0.0) - a.get(name, 0.0)


def phase_s(ctx, phase):
    return delta(ctx, f"tpu_engine_loop_{phase}_seconds_total")


def step_s(ctx):
    """Seconds the owner loop spent in steps: the seven phases that
    partition a step (a sub-phase's seconds are inside its parent's)."""
    return sum(phase_s(ctx, p) for p in STEP_PHASES)


def ratio(num, den, scale=100.0):
    """``scale * num / den``; 0.0 where nothing happened."""
    return scale * num / den if den > 0 else 0.0
