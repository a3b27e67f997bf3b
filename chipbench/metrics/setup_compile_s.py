"""Set-up time spent compiling or reading the persistent compilation
cache: the program's ``compile`` spans that ran between process start
and the window's opening, summed (s)."""
from chipbench import compiles


def read(run):
    c = compiles.spans()
    if c is None:
        return None
    t_process = run.t_open - run.setup_s
    return sum(e - s for s, e in c if s >= t_process and e <= run.t_open)
