"""Share of the owner loop's step time in ``readback``, the one phase in
which the host waits for the chip; the rest is the host's own work, during
which the chip has at most one dispatch to run.  ``loop_device_wait.batch``
and ``.chat`` are this one reading, named apart because each moves another
end-to-end metric."""

from chipbench.readers._loop import phase_s, ratio, step_s


def read(ctx):
    return ratio(phase_s(ctx, "readback"), step_s(ctx))
