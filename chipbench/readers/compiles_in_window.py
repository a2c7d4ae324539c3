"""XLA compilations the replica logged between the window's marks
(``JAX_LOG_COMPILES`` lines, stamped by the launcher's log format; a hit in
the persistent cache logs one too).  Should be 0."""


def read(ctx):
    w0, w1 = ctx["window_wall"]
    count = 0
    with open(ctx["serve_err"], errors="replace") as f:
        for line in f:
            if "Finished XLA compilation" not in line:
                continue
            try:
                stamp = float(line.split(" ", 1)[0])
            except ValueError:
                continue
            count += w0 <= stamp <= w1
    return count
