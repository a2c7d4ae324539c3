"""How late the load generator ran: sent minus due, all requests."""

from chipbench.stats import percentile


def read(ctx):
    return percentile([(r.sent - r.due) * 1e3 for r in ctx["results"] if r.sent], 95)
