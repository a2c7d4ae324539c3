"""Share of the owner thread's time spent waiting for work (no queued
request, no occupied slot): ``tpu_engine_loop_idle_seconds_total`` over idle
plus the seven step phases.  That part of the device's idle time is the
offered load's, not the loop's."""

from chipbench.readers._loop import phase_s, ratio, step_s


def read(ctx):
    idle = phase_s(ctx, "idle")
    return ratio(idle, idle + step_s(ctx))
