"""Share of the owner loop's step time in the ``prefill`` phase (chunk
dispatches, the eager graft into pages, first-token sampling):
``tpu_engine_loop_prefill_seconds_total`` over the seven step phases."""

from chipbench.readers._loop import phase_s, ratio, step_s


def read(ctx):
    return ratio(phase_s(ctx, "prefill"), step_s(ctx))
