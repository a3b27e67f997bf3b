"""Share of the traced slice in which no operation ran on the device
while the host sat in the program's ``wait`` span, blocked on a step's
logits (%).  The host waits there for device work, so a sound trace
reads about 0; a long stretch means the trace missed work or the device
stalled on something that is not an XLA operation.

The program's ``wait`` spans come from its registry (``run.spans``, on
the harness clock).  The harness opened the trace's window mark at
``run.trace_t0``, which puts a span on the trace's clock to within
microseconds; the trace's own events of each wait's parent (``sync``,
or ``collectives`` on a mesh) then fix the offset, by the median
distance from each to the program's nearest span of the same name."""
import statistics

from chipbench import tracing

PARENTS = ("sync", "collectives")


def _offset_ns(tr, mine):
    """Trace clock less anchored program clock, by the parents' edges
    that the window did not clip."""
    shift = []
    for h in tr.host:
        if h.name not in PARENTS or not mine:
            continue
        if h.start_ns > tr.t0_ns:
            edge, x = 0, h.start_ns
        elif h.end_ns < tr.t1_ns:
            edge, x = 1, h.end_ns
        else:
            continue
        near = min((m[edge] for m in mine), key=lambda y: abs(y - x))
        shift.append(x - near)
    return statistics.median(shift) if shift else 0.0


def read(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None

    def on_trace(e):
        s = tr.t0_ns + (e["t"] - run.trace_t0) * 1e9
        return s, s + e["d"] * 1e9

    waits = [on_trace(e) for e in run.spans if e["name"] == "wait"]
    if not waits:
        return None
    off = _offset_ns(tr, [on_trace(e) for e in run.spans
                          if e["name"] in PARENTS])
    waits = [(max(s + off, tr.t0_ns), min(t + off, tr.t1_ns))
             for s, t in waits]
    waits = [w for w in waits if w[1] > w[0]]
    busy = [[(e.start_ns, e.end_ns) for e in tr.ops if e.plane == p]
            for p in sorted({e.plane for e in tr.ops})] or [[]]
    # idle under wait = |wait U busy| - |busy|, averaged over the devices
    idle = [tracing.union_ns(waits + b) - tracing.union_ns(b) for b in busy]
    return 100.0 * sum(idle) / len(idle) * 1e-9 / tr.window_s
