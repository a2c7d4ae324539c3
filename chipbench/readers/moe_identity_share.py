"""Of the window's expert assignments (``tpu_engine_moe_assignments_total``,
every kind: top-k a real token and expert layer, prefill and decode), the
share that went to zero-computation experts
(``tpu_engine_moe_identity_assignments_total``): work the architecture
spares.  Uniform routing reads Z / (E + Z).  Nothing to read on a program
without the counters."""

from chipbench.readers._loop import delta, ratio


def read(ctx):
    if "tpu_engine_moe_identity_assignments_total" not in ctx["scraped"]["after"]:
        return None
    return ratio(delta(ctx, "tpu_engine_moe_identity_assignments_total"), delta(ctx, "tpu_engine_moe_assignments_total"))
