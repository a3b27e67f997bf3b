"""Median time to first token of every request due in the window,
counted from when it was due; a request with no first token at the
close enters with its wait so far (s)."""
from chipbench import stats


def read(run):
    sample, _ = stats.ttft_sample(run.records, run.t_open, run.t_close)
    return stats.percentile(sample, 50)
