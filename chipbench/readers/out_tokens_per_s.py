"""All output tokens received inside the window over the whole window."""


def read(ctx):
    t0, seconds = ctx["window"]
    got = sum(1 for r in ctx["results"] for t in r.token_times if t0 <= t < t0 + seconds)
    return got / seconds
