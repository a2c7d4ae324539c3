"""90th percentile of first streamed token minus the time the request was
due, over the requests that were due ``CLEAR_S`` or more before the
profiler's capture began (every request where the run has no capture).
Starting the profiler stalls the replica's loop for seconds, so the waits of
the window's last requests read the tracer and not the replica.  The choice
is by the time a request was DUE, never by how it fared.  A request that
failed, was refused or never streamed a token misses at the tail: it counts
as the whole window plus the wait after it.

Judged end to end as ``ttft_p90_ms`` until the driver's check read its
spread at 6-7 % of the median, over half of the largest bound a metric may
have (PERF.md section 2); per layer since."""

from chipbench.stats import DRAIN_S, capture_span, percentile

CLEAR_S = 1.5  # over the longest wait that a sound window reads (1.11 s)
MIN_CLEAR = 10  # fewer requests than this give no 90th percentile


def read(ctx):
    t0, seconds = ctx["window"]
    # The capture as planned, not as it went: a first capture that failed
    # and was made again has stalled the loop all the same.
    traced = getattr(ctx.get("args"), "trace", 0)
    last_due = t0 + capture_span(seconds)[0] - CLEAR_S if traced else float("inf")
    miss = (seconds + DRAIN_S) * 1e3
    early = [r for r in ctx["results"] if r.due <= last_due]
    # A window too short to hold ten requests clear of the capture (a
    # rehearsal of seconds) reads them all: the cell's line never lacks the metric.
    waits = [(r.token_times[0] - r.due) * 1e3 if r.token_times and r.error is None else miss
             for r in (early if len(early) >= MIN_CLEAR else ctx["results"])]
    return percentile(waits, 90) if waits else None
