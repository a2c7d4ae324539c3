"""Share of the decode dispatches that ran the single-step program
(``jit_step``: a host round trip a token) in place of a block
(``jit_block``): what the engine falls back to while a request waits or a
prompt streams in (PERF.md Findings, "the knee is a cliff")."""

from chipbench.readers._loop import delta, ratio


def read(ctx):
    single = delta(ctx, "tpu_engine_decode_dispatches_step_total")
    return ratio(single, single + delta(ctx, "tpu_engine_decode_dispatches_block_total"))
