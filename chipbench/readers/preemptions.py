"""Requests preempted for pages in the window: difference of
``tpu_engine_preemptions_total``."""


def read(ctx):
    a, b = ctx["scraped"]["before"], ctx["scraped"]["after"]
    name = "tpu_engine_preemptions_total"
    if name not in b:
        return None
    return b[name] - a.get(name, 0)
