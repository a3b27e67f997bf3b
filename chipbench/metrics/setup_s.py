"""Process start until the window opens: weights, kernel block tuning,
compiles (or compile-cache reads), warm-up and, for a backlog, the
first wave's prefill (s)."""


def read(run):
    return run.setup_s
