"""Of the seconds in the traced run's listed idle gaps
(``trace_reduced["idle_gaps"]``: the device's longest gaps, each under the
host event that covers most of it), the share under one of the owner
loop's own phases (``engine.<phase>``, models/engine_profiler.py).  The
rest lies under the runtime's events or under nothing (``host_idle``).
``idle_gap_named.batch`` and ``.chat`` are this one reading."""

from chipbench.readers._loop import ratio


def read(ctx):
    reduced = ctx.get("trace_reduced")
    if not reduced:
        return None  # no trace: the run gives no traced line at all
    gaps = reduced["idle_gaps"]
    named = sum(s for label, s in gaps if label.startswith("engine."))
    return ratio(named, sum(s for _, s in gaps))
