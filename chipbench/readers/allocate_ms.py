"""The parent's clock around the ``Allocate`` RPC to the real daemon."""


def read(ctx):
    return ctx["allocate_ms"]
