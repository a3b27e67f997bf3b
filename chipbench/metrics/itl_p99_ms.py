"""99th percentile of every gap between consecutive output tokens of a
request, both tokens in the window (ms)."""
from chipbench import stats


def read(run):
    v = stats.percentile(stats.token_gaps(run.records, run.t_open,
                                          run.t_close), 99)
    return None if v is None else v * 1e3
