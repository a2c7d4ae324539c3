"""Of the bytes the traced decode steps need (``decode_step`` of the
configuration's family: weights, the live contexts' keys and values, the
live slots' recurrent state), the share that is the state, read and written
once a step (the family's ``decode_state_bytes``: from the program's gauge
``tpu_engine_slot_state_bytes`` and the contexts live at the capture's
middle).  Nothing to read where the family counts no state or the program
has no such gauge."""

from chipbench import families
from chipbench.readers._traced import live_contexts


def read(ctx):
    m = ctx["cell"].config["model"]
    family = families.of(m)
    contexts = live_contexts(ctx)
    gauge = ctx["scraped"]["after"].get("tpu_engine_slot_state_bytes")
    if not contexts or not gauge or not hasattr(family, "decode_state_bytes"):
        return None
    return 100.0 * family.decode_state_bytes(m, contexts, ctx) / family.decode_step(m, contexts, ctx)[1]
