"""Compiles and compile-cache reads that started inside the window: the
program's ``compile`` spans starting in [t_open, t_close); every program
the window runs was compiled in set-up, so 0 is sound (count)."""
from chipbench import compiles


def read(run):
    c = compiles.spans()
    if c is None:
        return None
    return sum(1 for s, _ in c if run.t_open <= s < run.t_close)
