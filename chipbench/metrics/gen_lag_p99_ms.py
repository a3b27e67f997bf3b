"""Load generator lag: 99th percentile of add() time minus due time over
the requests due in the window; one not yet added at the close enters
with its lag so far (ms)."""
from chipbench import stats


def read(run):
    lags = [((r.added if r.added is not None and r.added < run.t_close
              else run.t_close) - r.due)
            for r in run.records if run.t_open <= r.due < run.t_close]
    v = stats.percentile(lags, 99)
    return None if v is None else v * 1e3
