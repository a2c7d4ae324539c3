"""Shared by the readers of the device trace: which programs decode, and
the least time the chip could take for their steps (FLOPs and bytes as the
configuration's family counts them, with the run's ``ctx`` to count from)."""

from chipbench import families, peaks


def decode_events(ctx):
    """(seconds, steps) of every decode program event on the busiest device."""
    reduced = ctx.get("trace_reduced")
    if not reduced:
        return []
    names = tuple(ctx["cell"].config["programs"]["decode"])
    return [(d, n) for name, d, n in reduced["program_events"] if name.startswith(names)]


def live_contexts(ctx):
    """Context lengths of the requests that were decoding at the middle of
    the capture, from the client's own record of each token's arrival."""
    if not ctx.get("capture_interval"):
        return []
    a, b = ctx["capture_interval"]
    mid = (a + b) / 2.0
    out = []
    for r in ctx["results"]:
        if r.token_times and r.token_times[0] <= mid <= r.token_times[-1]:
            out.append(r.prompt_tokens + sum(1 for t in r.token_times if t <= mid))
    return out


def step_least_s(ctx, contexts):
    """(least seconds of one decode step, which bound) on one chip of the
    cell's ``count``: weights and heads divide over the chips of a tp mesh."""
    kind = ctx["device"]["kind"]
    peak = peaks.peaks(kind)
    m = ctx["cell"].config["model"]
    f, b = families.of(m).decode_step(m, contexts, ctx)
    chips = ctx["cell"].chips
    t_f, t_b = f / chips / peak["bf16_flops"], b / chips / peak["hbm_bytes_per_s"]
    return max(t_f, t_b), "flops" if t_f > t_b else "bytes"


def idle_share(ctx):
    reduced = ctx.get("trace_reduced")
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
