"""How much of its own host work the owner loop spends computing rather
than waiting: the loop thread's CPU seconds in the step phases that do not
wait for the chip (``tpu_engine_loop_host_cpu_seconds_total``,
``models/engine_profiler.py``) over those phases' wall seconds: the seven
step phases less ``readback``, ``spec_verify`` and the first token's sync
(``prefill.first_token``), so both cover the same instants.  Low says the
loop waits (for the GIL the streaming threads hold, or a sync no phase
names); high says it runs its own Python.  A program without the counter
reads 0.0, as ``_loop.py`` says."""

from chipbench.readers._loop import delta, phase_s, ratio, step_s


def read(ctx):
    host = step_s(ctx) - phase_s(ctx, "readback") - phase_s(ctx, "spec_verify") - phase_s(ctx, "first_token")
    return ratio(delta(ctx, "tpu_engine_loop_host_cpu_seconds_total"), host)
