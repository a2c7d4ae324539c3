"""Assignments to held experts that the expert layer did not compute, over
the window: difference of ``tpu_engine_moe_dropped_assignments_total``
(models/moe.py counts, on the device, what each branch computed).  The layer
is dropless: this reads 0.  Nothing to read on a program without the counter."""

from chipbench.readers._loop import delta


def read(ctx):
    if "tpu_engine_moe_assignments_total" not in ctx["scraped"]["after"]:
        return None
    return delta(ctx, "tpu_engine_moe_dropped_assignments_total")
