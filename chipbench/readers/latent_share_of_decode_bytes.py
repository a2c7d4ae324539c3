"""Of the bytes the traced decode steps need (``decode_step`` of the
configuration's family), the share that is the latent cache: each live
context's rows read once and one row written (the family's
``decode_latent_bytes``: the program's gauge
``tpu_engine_cache_bytes_per_token`` times the positions live at the
capture's middle).  Nothing to read where the family counts no latent cache
or the program has no such gauge."""

from chipbench import families
from chipbench.readers._traced import live_contexts


def read(ctx):
    m = ctx["cell"].config["model"]
    family = families.of(m)
    contexts = live_contexts(ctx)
    gauge = ctx["scraped"]["after"].get("tpu_engine_cache_bytes_per_token")
    if not contexts or not gauge or not hasattr(family, "decode_latent_bytes"):
        return None
    return 100.0 * family.decode_latent_bytes(m, contexts, ctx) / family.decode_step(m, contexts, ctx)[1]
