"""Host milliseconds making the coming writes addressable
(``tpu_engine_loop_frontier_seconds_total``: page allocation, preemption and
the publication of the grown pages to the device chain,
``engine_paging.py::_ensure_frontier``) per decode dispatch
(``tpu_engine_decode_dispatches_step_total`` plus ``..._block_total``) over
the window.  All three counters exist since the loop's phases were counted;
a program without them reads 0.0, as ``_loop.py`` says."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    dispatches = delta(ctx, "tpu_engine_decode_dispatches_step_total") + delta(ctx, "tpu_engine_decode_dispatches_block_total")
    return ratio(phase_s(ctx, "frontier"), dispatches, 1e3)
