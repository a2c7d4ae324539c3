"""Device time of one prefill chunk program (the configuration's
``programs.prefill``: ``jit_run``, where a mixer's chunked scan runs): the
median over its events in the traced window, whatever their batch."""

from statistics import median


def read(ctx):
    reduced = ctx.get("trace_reduced")
    if not reduced:
        return None
    names = tuple(ctx["cell"].config["programs"]["prefill"])
    events = [d for name, d, _ in reduced["program_events"] if name.startswith(names)]
    return median(events) * 1e3 if events else None
