"""Share of the page pool that live requests held, averaged over the
window's steps: the page pool's used pages after each step over its
pages, the reserved null page left out (%)."""
from chipbench import stats


def read(run):
    return stats.pool_fill(run.window_steps(), run.pages)
