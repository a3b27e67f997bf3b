"""Host time per scheduler step in the window: the self time of the
program's ``admit`` and ``host`` spans (their duration less the spans
nested in them, such as the ``sync`` wait for the device), over the steps
in the window (ms)."""
from chipbench import spans


def read(run):
    steps = len(run.window_steps())
    if steps == 0:
        return None
    own = spans.self_seconds(run.spans, run.t_open, run.t_close,
                             ("admit", "host"))
    return own / steps * 1e3
