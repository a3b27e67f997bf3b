"""Median wait from when a request was due to the start of the scheduler
step that admitted it, over the requests due in the window; one not
admitted at the close enters with its wait so far (s)."""
from chipbench import stats


def read(run):
    waits = [((r.admitted if r.admitted is not None
               and r.admitted < run.t_close else run.t_close) - r.due)
             for r in run.records if run.t_open <= r.due < run.t_close]
    return stats.percentile(waits, 50)
