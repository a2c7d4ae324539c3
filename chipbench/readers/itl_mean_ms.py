"""Mean over all gaps between consecutive streamed tokens: the time per
output token once a request decodes, every block, prefill met on the way
and host stall counted."""

from chipbench.stats import token_gaps_ms


def read(ctx):
    gaps = token_gaps_ms(ctx["results"])
    return sum(gaps) / len(gaps) if gaps else None
