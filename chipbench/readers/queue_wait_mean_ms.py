"""Mean queue wait of the requests admitted in the window: difference over
the window of sum and count of ``tpu_engine_queue_wait_seconds``."""


def read(ctx):
    a, b = ctx["scraped"]["before"], ctx["scraped"]["after"]
    name = "tpu_engine_queue_wait_seconds"
    n = b.get(name + "_count", 0) - a.get(name + "_count", 0)
    if n <= 0:
        return None
    return (b[name + "_sum"] - a.get(name + "_sum", 0)) / n * 1e3
