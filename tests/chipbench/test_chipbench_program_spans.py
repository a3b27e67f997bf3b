"""The readers of the program's step, wait/fetch and compile spans, on
synthetic runs; and the self time of the host spans, which the new spans
must leave as it was."""
import itertools
import json
import pathlib
import sys
import time
import types

import numpy as np
import pytest

from chipbench import cells, harness, spans, tracing
from chipbench.tracing import Event
from repro.serving import telemetry

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def read(name, run):
    return cells.metric_reader(name)(run)


def make_run(**kw):
    run = harness.Run(cell=None, seed=0, seconds=10.0, dims=None,
                      device_kind="TPU v5 lite", slots=2, page_size=16,
                      pages=10)
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def span(name, t, d):
    return {"name": name, "t": t, "d": d}


# --------------------------------------------------------------------------- #
def test_fetch_ms_per_step_sums_the_window_fetch_spans_over_its_steps():
    run = make_run(t_open=10.0, t_close=20.0, steps=[
        harness.StepRecord(index=i, t_start=t) for i, t in
        enumerate((9.0, 10.0, 12.0, 14.0, 16.0, 20.5))])
    run.spans = [span("fetch", 9.5, 0.5),      # before the window
                 span("fetch", 10.5, 0.020), span("fetch", 12.5, 0.022),
                 span("wait", 13.0, 0.9), span("fetch", 19.9, 0.018),
                 span("fetch", 20.6, 0.3)]     # after it
    # 60 ms of fetch over the window's 4 steps
    assert read("fetch_ms_per_step", run) == pytest.approx(15.0)


def test_fetch_ms_per_step_reads_nothing_without_fetch_spans():
    run = make_run(t_open=0.0, t_close=5.0,
                   steps=[harness.StepRecord(index=0, t_start=1.0)],
                   spans=[span("sync", 1.5, 0.02)])
    assert read("fetch_ms_per_step", run) is None


# --------------------------------------------------------------------------- #
T0 = 1000.0  # the harness clock when the traced slice opened


def trace_run(events, program, anchor_error_ns=0.0):
    """A run whose trace is reduced as the harness reduces it, and whose
    program spans (name, start_ns, dur_ns on the trace's clock) sit on
    the harness clock, anchored ``anchor_error_ns`` off."""
    tr = tracing.reduce(events, harness.HOST_SPANS)
    return make_run(trace=tr, trace_t0=T0, spans=[
        span(name, T0 + (s - tr.t0_ns - anchor_error_ns) * 1e-9, d * 1e-9)
        for name, s, d in program])


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


US = 1e3
WINDOW = [ev(HOST, "python", tracing.WINDOW, 0, 1000 * US)]
OPS = [ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)", 0,
          300 * US),
       ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)",
          500 * US, 300 * US)]
# a step's spans: sync 250-450 us, wait 250-400 us, fetch 400-450 us
STEP = [("step", 0, 1000 * US), ("sync", 250 * US, 200 * US),
        ("wait", 250 * US, 150 * US), ("fetch", 400 * US, 50 * US)]


def host_events(program):
    return [ev(HOST, "python", n, s, d) for n, s, d in program]


@pytest.mark.parametrize("anchor_error_ns", [0.0, 30 * US, -45 * US])
def test_wait_idle_share_counts_idle_device_time_under_wait(
        anchor_error_ns):
    # idle 300-500 and 800-1000 us; wait covers 250-400 us: 100 us of it
    # idle.  The trace keeps the sync events, which fix the anchor.
    run = trace_run(WINDOW + OPS + host_events(STEP), STEP, anchor_error_ns)
    assert {h.name for h in run.trace.host} == {"sync"}
    assert read("wait_idle_share", run) == pytest.approx(10.0)


def test_wait_idle_share_aligns_on_a_parent_the_window_clipped():
    # the slice opens inside a sync: only its end is the program's
    program = [("sync", -100 * US, 200 * US), ("wait", -100 * US, 150 * US),
               ("fetch", 50 * US, 50 * US)]
    ops = [ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)",
              -100 * US, 130 * US),
           ev(DEV, tracing.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)",
              100 * US, 900 * US)]
    run = trace_run(WINDOW + ops + host_events(program), program, 20 * US)
    # idle 30-100 us; wait, clipped to the slice, covers 0-50 us
    assert read("wait_idle_share", run) == pytest.approx(2.0)


def test_wait_idle_share_is_zero_when_the_device_ran_under_every_wait():
    program = [("sync", 100 * US, 200 * US), ("wait", 100 * US, 150 * US)]
    run = trace_run(WINDOW + OPS + host_events(program), program)
    assert read("wait_idle_share", run) == 0.0


def test_wait_idle_share_reads_nothing_without_wait_spans():
    # a program with no wait span (the sync alone), and a run untraced
    program = [("sync", 250 * US, 400 * US)]
    run = trace_run(WINDOW + OPS + host_events(program), program)
    assert read("wait_idle_share", run) is None
    assert read("wait_idle_share", make_run()) is None


def test_recorded_chip_trace_with_a_device_gap_under_wait():
    """153 ms of a traced decode-long window on a TPU v5 lite (PR 13),
    op names cut to 160 characters: the end of a step whose last device
    operation ended 5.3 ms before the host's ``wait`` returned, a copy of
    the logits that stalled for 131 ms (``fetch``), and the next step's
    first operations.  The trace holds the program's spans as profiler
    annotations; the run's program spans are made from them, anchored
    40 us off."""
    raw = [Event(**e) for e in json.loads(
        (pathlib.Path(__file__).parent / "data"
         / "trace_wait_gap.json").read_text())]
    program = [(e.name, e.start_ns, e.dur_ns) for e in raw
               if e.plane == HOST and e.name in ("sync", "wait", "fetch")]
    run = trace_run(raw, program, anchor_error_ns=40 * US)
    tr = run.trace
    # by hand: the idle stretches of the device under the trace's waits
    ops = [(e.start_ns, e.end_ns) for e in tr.ops]
    idle = tracing.gaps_ns(ops, tr.t0_ns, tr.t1_ns)
    waits = [w for w in tracing.reduce(raw).host if w.name == "wait"]
    under = sum(max(0.0, min(t, w.end_ns) - max(s, w.start_ns))
                for s, t in idle for w in waits)
    assert 5e6 < under < 6e6
    assert read("wait_idle_share", run) == pytest.approx(
        100.0 * under / (tr.t1_ns - tr.t0_ns))
    # the long gap, named by the harness's spans, is the stalled copy's
    gaps = tr.idle_gaps()
    assert gaps[0][0] == "sync" and gaps[0][1] > 0.14
    fetch = [e for e in run.spans if e["name"] == "fetch"]
    assert len(fetch) == 1 and fetch[0]["d"] > 0.13
    # every gap of a millisecond or more is named by a program span
    assert all(name != "-" for name, g in gaps if g >= 1e-3)


# --------------------------------------------------------------------------- #
@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry for the program's compile spans."""
    reg = telemetry.Telemetry()
    monkeypatch.setattr(telemetry, "_DEFAULT", reg)
    return reg


def compile_at(reg, start, end, source="backend"):
    """A compile span from ``start`` to ``end`` on the harness clock
    (time.perf_counter), recorded the way JAX reports it: wall clock."""
    to_wall = time.time() - time.perf_counter()
    reg.span_at("compile", start + to_wall, end + to_wall, fun="jit(f)",
                source=source)


def test_window_compiles_counts_compile_spans_starting_in_the_window(
        registry):
    now = time.perf_counter()
    run = make_run(t_open=now - 100.0, t_close=now - 49.0, setup_s=60.0)
    compile_at(registry, now - 150.0, now - 140.0)  # set-up
    compile_at(registry, now - 90.0, now - 89.0)    # in the window
    compile_at(registry, now - 50.0, now - 48.0, "cache")  # in, ends after
    compile_at(registry, now - 20.0, now - 19.0)    # after the close
    assert read("window_compiles", run) == 2


def test_setup_compile_s_sums_compile_spans_of_set_up(registry):
    now = time.perf_counter()
    run = make_run(t_open=now - 100.0, t_close=now - 49.0, setup_s=60.0)
    compile_at(registry, now - 170.0, now - 165.0)  # before process start
    compile_at(registry, now - 150.0, now - 140.0)
    compile_at(registry, now - 130.0, now - 127.5, "cache")
    compile_at(registry, now - 101.0, now - 99.0)   # ends in the window
    assert read("setup_compile_s", run) == pytest.approx(12.5, abs=1e-3)
    assert read("window_compiles", run) == 0


def test_compile_readers_read_nothing_from_a_program_without_compile_spans(
        monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry",
                        types.ModuleType("repro.serving.telemetry"))
    run = make_run(t_open=1.0, t_close=2.0, setup_s=1.0)
    assert read("window_compiles", run) is None
    assert read("setup_compile_s", run) is None


# --------------------------------------------------------------------------- #
def test_traced_run_reports_the_program_span_metrics(tmp_path):
    import tiny

    cell = tiny.make(tmp_path)
    res, _ = harness.run_cell(cell, 2**31 + 99, 2.0, trace=True,
                              t_process=0.0, device=tiny.CPU)
    assert res["correct"], res["checks"]
    for name in ("fetch_ms_per_step", "window_compiles", "setup_compile_s"):
        assert name in res["metrics"], name
    assert res["metrics"]["fetch_ms_per_step"]["value"] > 0
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["metrics"]["setup_compile_s"]["value"] > 0
    # the CPU has no device plane: the device reader reads nothing
    assert "wait_idle_share" not in res["metrics"]


# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def smoke_events():
    """The engine spans of a scripted continuous run, every span a whole
    number of clock ticks long (the clock advances 1 ms each reading)."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.serving import ContinuousScheduler, Request

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    eng = serve.Engine(cfg, slots=2, max_seq=16, cache_impl="paged",
                       page_size=4, stochastic_kv=False)
    ticks = itertools.count()
    tel = telemetry.Telemetry(clock=lambda: next(ticks) * 1e-3)
    sched = ContinuousScheduler(eng, chunk=4, telemetry=tel)
    rng = np.random.default_rng(4)
    for i in range(3):
        sched.add(Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=6),
                          gen=3))
    sched.run()
    return [dict(e, t=e["ts"] * 1e-6, d=e["dur"] * 1e-6) for e in tel.events
            if e["ph"] == "X"]


def test_admit_and_host_self_time_is_the_same_without_the_new_spans(
        smoke_events):
    new = {"step", "wait", "fetch"}
    assert {e["name"] for e in smoke_events} >= new
    old = [e for e in smoke_events if e["name"] not in new]
    for names in (("admit",), ("host",), ("admit", "host")):
        with_new = spans.self_seconds(smoke_events, 0.0, 1e9, names)
        without = spans.self_seconds(old, 0.0, 1e9, names)
        assert with_new > 0
        assert with_new == pytest.approx(without, abs=1e-9)
    # sync's own time is what wait and fetch do not cover
    sync = spans.self_seconds(smoke_events, 0.0, 1e9, ("sync",))
    assert sync == pytest.approx(
        spans.self_seconds(old, 0.0, 1e9, ("sync",))
        - sum(e["d"] for e in smoke_events if e["name"] in ("wait", "fetch")),
        abs=1e-9)
