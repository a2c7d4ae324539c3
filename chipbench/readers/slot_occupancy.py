"""Share of the decode slots in use: mean of the ``tpu_engine_active_slots``
gauge over the window's samples (every 0.5 s), over the slots.
(``tpu_engine_steps_total`` counts a decode BLOCK of up to ``decode_block``
steps once, so tokens over steps says nothing about slots.)"""


def read(ctx):
    t0, seconds = ctx["window"]
    got = [s["tpu_engine_active_slots"] for t, s in ctx["scraped"]["samples"]
           if t0 <= t <= t0 + seconds and "tpu_engine_active_slots" in s]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / ctx["slots"]
