"""95th percentile over all gaps between consecutive streamed tokens of all
requests of the window, on the client's clock.  ``decode_block=16`` streams
tokens in bursts, so one gap in sixteen is a whole block: the 95th
percentile lies inside those, at one block's period."""

from chipbench.stats import percentile, token_gaps_ms


def read(ctx):
    return percentile(token_gaps_ms(ctx["results"]), 95)
