"""Host milliseconds in the call that enqueues a decode program
(``tpu_engine_loop_enqueue_seconds_total``: the ``dispatch.enqueue`` phase
around ``jit_step`` / ``jit_block`` in ``engine.py::_dispatch_decode``, its
operands already built) per decode dispatch
(``tpu_engine_decode_dispatches_step_total`` plus ``..._block_total``) over
the window.  A program without the counter reads 0.0, as ``_loop.py``
says."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    dispatches = delta(ctx, "tpu_engine_decode_dispatches_step_total") + delta(
        ctx, "tpu_engine_decode_dispatches_block_total")
    return ratio(phase_s(ctx, "enqueue"), dispatches, 1e3)
