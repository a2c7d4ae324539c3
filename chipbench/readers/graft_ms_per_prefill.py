"""Host milliseconds of the eager graft (a prompt's K/V copied into pages,
``tpu_engine_loop_graft_seconds_total``) per request prefilled
(``tpu_engine_requests_total``; a preemption's resume grafts again without
counting, and ``preemptions`` stands beside this metric)."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    return ratio(phase_s(ctx, "graft"), delta(ctx, "tpu_engine_requests_total"), 1e3)
