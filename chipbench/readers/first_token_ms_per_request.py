"""Host milliseconds sampling each request's admission token
(``tpu_engine_loop_first_token_seconds_total``: the
``prefill.first_token`` phase, ``engine_admission.py::_sample_first_token``,
which waits on the owner loop's thread for the prefill program) per
finished request (``tpu_engine_requests_total``) over the window.  A
program without the counter reads 0.0, as ``_loop.py`` says."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    return ratio(phase_s(ctx, "first_token"), delta(ctx, "tpu_engine_requests_total"), 1e3)
