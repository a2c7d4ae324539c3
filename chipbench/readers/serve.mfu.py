"""The whole replica's share of the chips' peak: FLOPs the model needs for
every prompt token prefilled and every output token received in the window
(``request_flops`` of the configuration's family, from its shapes), over
the window times ``count`` times the bf16 peak."""

from chipbench import families, peaks


def read(ctx):
    t0, seconds = ctx["window"]
    m = ctx["cell"].config["model"]
    request_flops = families.of(m).request_flops
    total = 0.0
    for r in ctx["results"]:
        got = sum(1 for t in r.token_times if t0 <= t < t0 + seconds)
        if got:
            # Prefill counts with the first token it produced.
            first_in = t0 <= r.token_times[0] < t0 + seconds
            whole = request_flops(m, r.prompt_tokens, len(r.token_times))
            prefill = request_flops(m, r.prompt_tokens, 1)
            decode = whole - prefill
            share = (got - first_in) / max(len(r.token_times) - 1, 1)
            total += (prefill if first_in else 0.0) + decode * share
    if total <= 0:
        return None
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * total / (seconds * ctx["device"]["count"] * peak)
