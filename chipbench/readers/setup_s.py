"""Process start of the run to the window's start: daemon, Allocate, child
start, weights or state, warm-up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
