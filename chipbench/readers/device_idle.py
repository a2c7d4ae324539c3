"""100 x (1 - busy_s / window_s) of the traced window; ``device_idle.chat``,
``.batch`` and ``.train`` are this one reading, named apart because each
moves another end-to-end metric."""

from chipbench.readers._traced import idle_share


def read(ctx):
    return idle_share(ctx)
