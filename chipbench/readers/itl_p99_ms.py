"""99th percentile over all gaps between consecutive streamed tokens: among
the blocks that a prefill lengthened.  It sits on an edge (how many blocks
met one or two prefill chunks) and flips between about 315 and 430 ms from
seed to seed on the chip, so it is a per-layer reading and not judged."""

from chipbench.stats import percentile, token_gaps_ms


def read(ctx):
    return percentile(token_gaps_ms(ctx["results"]), 99)
