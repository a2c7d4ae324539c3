"""Host milliseconds building prefill jobs
(``tpu_engine_loop_start_prefill_seconds_total``: the zero dense cache of
an admission group, its host operands and the list building,
``engine_admission.py::_start_prefill``) per admitted request
(``tpu_engine_requests_total``) over the window.  Both counters exist since
the loop's phases were counted; a program without them reads 0.0, as
``_loop.py`` says."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    return ratio(phase_s(ctx, "start_prefill"), delta(ctx, "tpu_engine_requests_total"), 1e3)
