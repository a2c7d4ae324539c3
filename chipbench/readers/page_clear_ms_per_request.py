"""Host milliseconds to tear one slot down when its request ends
(``tpu_engine_loop_clear_slot_seconds_total`` over
``tpu_engine_cleared_slots_total``): eager writes to every layer's table
and the pages' release."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    return ratio(phase_s(ctx, "clear_slot"), delta(ctx, "tpu_engine_cleared_slots_total"), 1e3)
