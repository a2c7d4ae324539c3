"""The least time the chip could take for the traced decode steps (the
larger of FLOPs over peak and bytes over peak bandwidth; bytes are the
weights once a step plus the keys and values of the live contexts) over the
device time of the decode programs."""

import sys

from chipbench.readers._traced import decode_events, live_contexts, step_least_s


def read(ctx):
    events = decode_events(ctx)
    contexts = live_contexts(ctx)
    if not events or not contexts:
        print(f"decode_block_roofline: nothing to read ({len(events)} decode program events, "
              f"{len(contexts)} live contexts at the capture's middle)", file=sys.stderr)
        return None
    least, bound = step_least_s(ctx, contexts)
    steps, device_s = sum(n for _, n in events), sum(d for d, _ in events)
    print(f"decode_block_roofline: {steps} steps, {len(contexts)} live contexts, bound by {bound}", file=sys.stderr)
    return 100.0 * least * steps / device_s
