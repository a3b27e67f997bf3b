"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operation intervals, their union (busy time), the
time per operation name, the compiled programs (modules) that ran, and
the idle gaps named by the host span that was open during each.

The traced window is marked on the host by a ``TraceAnnotation`` named
:data:`WINDOW`; only what falls inside it counts, clipped to it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """A short name for an HLO operation event (whose name is the
        instruction's text, ``%name.N = type[shape]... op(...)``): the
        instruction's name without its number, and the first result
        shape.  Other events keep their name."""
        m = _HLO.match(self.name)
        if m is None:
            return self.name[:80]
        return f"{m.group(1)} {m.group(2)}"[:80]


_HLO = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)* = \(?(\w+\[[^\]]*\])?")


def load_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_ns(intervals: Sequence[Tuple[float, float]], t0: float,
            t1: float) -> List[Tuple[float, float]]:
    """Stretches of [t0, t1] in which no interval runs."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    """One traced window, reduced."""

    t0_ns: float
    t1_ns: float
    ops: List[Event]      # device operations, clipped to the window
    modules: List[Event]  # compiled programs that ran on the device
    host: List[Event]     # host spans (TraceAnnotations) in the window
    devices: int
    leaves: List[Event] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def _per_device(self):
        by: Dict[str, List[Tuple[float, float]]] = {}
        for e in self.ops:
            by.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
        return by

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        by = self._per_device()
        if not by:
            return 0.0
        return sum(union_ns(v) for v in by.values()) / len(by) * 1e-9

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation (short name), over the operations
        that hold no other (a loop's time is its body's)."""
        out: Dict[str, float] = {}
        for e in self.leaves:
            out[e.op] = out.get(e.op, 0.0) + e.dur_ns * 1e-9
        return out

    def matching_seconds(self, pattern: str) -> float:
        """Device seconds of the leaf operations whose full name matches."""
        rx = re.compile(pattern)
        return sum(e.dur_ns for e in self.leaves if rx.search(e.name)) * 1e-9

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of the first device, longest first, each named by
        the innermost host span open at its middle ("-" for none)."""
        by = self._per_device()
        if not by:
            return []
        plane = sorted(by)[0]
        out = []
        for s, e in gaps_ns(by[plane], self.t0_ns, self.t1_ns):
            mid = (s + e) / 2
            open_ = [h for h in self.host
                     if h.start_ns <= mid <= h.end_ns and h.name != WINDOW]
            name = min(open_, key=lambda h: h.dur_ns).name if open_ else "-"
            out.append((name, (e - s) * 1e-9))
        out.sort(key=lambda g: -g[1])
        return out


def reduce(events: List[Event], host_names: Optional[Iterable[str]] = None
           ) -> Optional[Trace]:
    """The window marked by :data:`WINDOW`, or None when the trace holds
    no such mark.  ``host_names`` keeps only those host spans (all when
    None)."""
    marks = [e for e in events if e.name == WINDOW and not is_device(e.plane)]
    if not marks:
        return None
    t0 = min(m.start_ns for m in marks)
    t1 = max(m.end_ns for m in marks)
    keep = set(host_names) if host_names is not None else None

    def clip(e: Event) -> Optional[Event]:
        s, t = max(e.start_ns, t0), min(e.end_ns, t1)
        if t <= s:
            return None
        return dataclasses.replace(e, start_ns=s, dur_ns=t - s)

    ops, modules, host = [], [], []
    for e in events:
        if is_device(e.plane):
            if e.line == OPS_LINE:
                c = clip(e)
                if c is not None:
                    ops.append(c)
            elif e.line == MODULES_LINE and t0 <= e.start_ns < t1:
                modules.append(e)
        elif keep is None or e.name in keep:
            c = clip(e)
            if c is not None:
                host.append(c)
    devices = len({e.plane for e in events if is_device(e.plane)
                   and e.line == OPS_LINE})
    return Trace(t0, t1, ops, modules, host, devices, leaf_ops(ops))


def leaf_ops(ops: List[Event]) -> List[Event]:
    """The operations inside which no other operation of the same device
    starts (a while loop holds its body's operations)."""
    out = []
    by: Dict[str, List[Event]] = {}
    for e in ops:
        by.setdefault(e.plane, []).append(e)
    for evs in by.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        for i, e in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt.start_ns >= e.end_ns:
                out.append(e)
    return out
