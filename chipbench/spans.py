"""Self time of the program's host spans (``serving/telemetry.py``).

Spans are recorded on one thread with context-manager nesting, so each
span's children are the spans that start inside it.  A span's self time
is its duration less its direct children's durations."""
from __future__ import annotations

from typing import Iterable, List


def self_seconds(spans: List[dict], t0: float, t1: float,
                 names: Iterable[str]) -> float:
    """Summed self time of the spans named ``names`` that start in
    [t0, t1).  Each span is a dict with ``name``, ``t`` (start) and ``d``
    (duration), in seconds."""
    names = set(names)
    evs = sorted(spans, key=lambda e: (e["t"], -e["d"]))
    children = [0.0] * len(evs)
    stack: List[int] = []
    eps = 1e-9
    for i, e in enumerate(evs):
        while stack and e["t"] >= evs[stack[-1]]["t"] + evs[stack[-1]]["d"] - eps:
            stack.pop()
        if stack:
            children[stack[-1]] += e["d"]
        stack.append(i)
    return sum(e["d"] - children[i] for i, e in enumerate(evs)
               if e["name"] in names and t0 <= e["t"] < t1)
