"""Tail and window arithmetic over request records (host clock, seconds).

Every end-to-end number is taken over the whole measured window
``[t_open, t_close)`` and over all requests in it: nothing is dropped to
quiet a tail.  A request that has no first token when the window closes
enters the TTFT sample with its wait so far (it is *censored*).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear interpolation), or None for an
    empty sample."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_sample(requests: Iterable, t_open: float,
                t_close: float) -> Tuple[List[float], int]:
    """(TTFT of every request due in the window, counted from when it was
    due; number of those censored at the close)."""
    out, censored = [], 0
    for r in requests:
        if not t_open <= r.due < t_close:
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first >= t_close:
            out.append(t_close - r.due)
            censored += 1
        else:
            out.append(first - r.due)
    return out, censored


def token_gaps(requests: Iterable, t_open: float,
               t_close: float) -> List[float]:
    """Every gap between consecutive output tokens of a request whose two
    tokens both fall in the window."""
    out = []
    for r in requests:
        t = np.asarray(r.token_times, np.float64)
        t = t[(t >= t_open) & (t < t_close)]
        out.extend(np.diff(t).tolist())
    return out


def tokens_in_window(requests: Iterable, t_open: float,
                     t_close: float) -> int:
    return sum(int(np.count_nonzero((np.asarray(r.token_times) >= t_open)
                                    & (np.asarray(r.token_times) < t_close)))
               for r in requests)


def counter_delta(before: Dict[str, float], after: Dict[str, float],
                  name: str) -> float:
    return float(after.get(name, 0.0)) - float(before.get(name, 0.0))


def pool_fill(steps, pages: int) -> Optional[float]:
    """Mean share of the pool's pages (the reserved null page left out)
    that requests held after each of ``steps`` (%), or None without
    steps."""
    if not steps or pages <= 1:
        return None
    return 100.0 * float(np.mean([s.used_pages for s in steps])) / (pages - 1)
