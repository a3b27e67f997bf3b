"""Output tokens emitted in the window, per second of the window."""
from chipbench import stats


def read(run):
    return stats.tokens_in_window(run.records, run.t_open,
                                  run.t_close) / run.seconds
