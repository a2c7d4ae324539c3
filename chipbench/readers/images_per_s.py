"""All images of all steps finished in the window over the whole window;
the last step is ended by ``block_until_ready`` (chipbench/train_child.py)."""


def read(ctx):
    return ctx["train"]["images_per_s"]
