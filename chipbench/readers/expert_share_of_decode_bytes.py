"""Of the bytes the traced decode steps need (``decode_step`` of the
configuration's family), the share that is the weights of the held experts
a step touched (the family's ``decode_expert_bytes``: the program's counters
``tpu_engine_moe_decode_experts_touched_total`` over
``tpu_engine_moe_decode_layer_steps_total``, the window's difference).
Nothing to read where the family counts no experts or the program has no
such counters."""

from chipbench import families
from chipbench.readers._traced import live_contexts


def read(ctx):
    m = ctx["cell"].config["model"]
    family = families.of(m)
    contexts = live_contexts(ctx)
    counted = "tpu_engine_moe_decode_layer_steps_total" in ctx["scraped"]["after"]
    if not contexts or not counted or not hasattr(family, "decode_expert_bytes"):
        return None
    return 100.0 * family.decode_expert_bytes(m, contexts, ctx) / family.decode_step(m, contexts, ctx)[1]
