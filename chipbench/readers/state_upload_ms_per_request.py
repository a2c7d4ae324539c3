"""Host milliseconds re-uploading the decode step's per-slot state per
finished request over the window: the rebuilds of the step state from the
host lists (``tpu_engine_loop_state_seconds_total``, the ``dispatch.state``
phase, ``engine.py::_rebuild_device_state``) plus the re-alignments of every
attention layer's device lengths (``tpu_engine_loop_realign_seconds_total``,
``finish.realign``, ``engine.py::_realign_seq_lens``), over
``tpu_engine_requests_total``.  A program without the counters reads 0.0,
as ``_loop.py`` says."""

from chipbench.readers._loop import delta, phase_s, ratio


def read(ctx):
    uploads = phase_s(ctx, "state") + phase_s(ctx, "realign")
    return ratio(uploads, delta(ctx, "tpu_engine_requests_total"), 1e3)
