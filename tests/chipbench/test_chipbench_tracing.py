"""Trace reduction: busy time as the union of device operations, time per
operation, step programs, and idle gaps named by the host span open in
them."""
import pytest

from chipbench import steps, tracing
from chipbench.tracing import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


EVENTS = [
    ev(HOST, "python", tracing.WINDOW, 100, 1000),     # window [100, 1100]
    ev(HOST, "python", "host", 100, 150),              # 100-250
    ev(HOST, "python", "sync", 300, 500),              # 300-800
    ev(HOST, "python", "admit", 850, 200),             # 850-1050
    ev(DEV, tracing.MODULES_LINE, "jit_step_paged(1)", 200, 500),
    # an operation of the last step, clipped to 100-150
    ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)", 50, 100),
    # a loop (200-700) and the operations of its body
    ev(DEV, tracing.OPS_LINE, "%while.2 = (s32[], f32[8]) while(%t)",
       200, 500),
    ev(DEV, tracing.OPS_LINE, "%_fused_decode_write_attend.3 = "
       "(f32[4,2]{1,0}) custom-call(%a), "
       'custom_call_target="tpu_custom_call"', 200, 150),
    ev(DEV, tracing.OPS_LINE, "%fusion.12 = f32[8]{0} fusion(%q)", 350, 50),
    ev(DEV, tracing.OPS_LINE, "%dot.7 = bf16[4,8]{1,0} dot(%x, %w)",
       600, 100),
    ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)", 1200, 50),
    ev(HOST, "python", "host", 2000, 10),              # after the window
]


def test_busy_is_the_union_of_operations_in_the_window():
    tr = tracing.reduce(EVENTS)
    # 100-150 and the loop's 200-700
    assert tr.busy_s == pytest.approx(550e-9)
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.devices == 1


def test_time_per_operation_counts_leaves_by_short_name():
    tr = tracing.reduce(EVENTS)
    ops = tr.op_seconds()
    assert ops == pytest.approx({"fusion f32[8]": 100e-9,
                                 "_fused_decode_write_attend f32[4,2]":
                                     150e-9,
                                 "dot bf16[4,8]": 100e-9})
    assert tr.matching_seconds(steps.KERNEL) == pytest.approx(150e-9)
    assert [m.name for m in tr.modules] == ["jit_step_paged(1)"]


def test_gaps_are_named_by_the_host_span_open_in_them():
    tr = tracing.reduce(EVENTS)
    gaps = tr.idle_gaps()
    # 150-200 (mid 175: host), 700-1100 (mid 900: admit)
    assert gaps == [("admit", pytest.approx(400e-9)),
                    ("host", pytest.approx(50e-9))]


def test_no_window_mark_reads_nothing():
    assert tracing.reduce([e for e in EVENTS
                           if e.name != tracing.WINDOW]) is None


def test_union_and_gaps_helpers():
    assert tracing.union_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracing.gaps_ns([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                       (5, 6)]


def test_recorded_chip_trace():
    """300 ms of a traced decode-long window on a TPU v5 lite (PR 12),
    trimmed: device operations and modules, and the program's spans."""
    import json
    import pathlib

    raw = json.loads((pathlib.Path(__file__).parent / "data"
                      / "trace_decode_long.json").read_text())
    tr = tracing.reduce([Event(**e) for e in raw])
    assert tr.window_s == pytest.approx(0.3)
    ops = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in raw
                 if e["line"] == tracing.OPS_LINE)
    # busy by hand: merge the operation intervals
    busy, end = 0.0, -1.0
    for s, t in ops:
        s, t = max(s, 0.0), min(t, 3e8)
        if t <= s:
            continue
        if s > end:
            busy, end = busy + t - s, t
        elif t > end:
            busy, end = busy + t - end, t
    assert tr.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < tr.busy_s < tr.window_s
    kernel = [e for e in raw if "tpu_custom_call" in e["name"]]
    assert len(kernel) == 2  # one per layer reached in the 300 ms
    assert tr.matching_seconds(steps.KERNEL) == pytest.approx(
        sum(e["dur_ns"] for e in kernel) * 1e-9)
    top = max(tr.op_seconds().items(), key=lambda kv: kv[1])
    assert top[0].startswith("_fused_decode_write_attend")
    assert len(tr.modules) == 1 and "step_paged" in tr.modules[0].name
    # the longest idle stretch is the host reading the step's logits
    assert tr.idle_gaps()[0][0] == "sync"
    assert sum(g for _, g in tr.idle_gaps()) == pytest.approx(
        tr.window_s - tr.busy_s)
