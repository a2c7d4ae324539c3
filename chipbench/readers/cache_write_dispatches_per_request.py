"""Dispatches of the compiled cache writers
(``tpu_engine_cache_write_dispatches_total``, graft and slot together) per
request prefilled (``tpu_engine_requests_total``) over the window: 2 while
a request costs one graft and one teardown, whatever the tree holds beside
the pages (a mixer's per-slot state rides the same two writers).  Nothing
to read on a program without the counter."""

from chipbench.readers._loop import delta, ratio


def read(ctx):
    if "tpu_engine_cache_write_dispatches_total" not in ctx["scraped"]["after"]:
        return None
    return ratio(delta(ctx, "tpu_engine_cache_write_dispatches_total"), delta(ctx, "tpu_engine_requests_total"), 1.0)
