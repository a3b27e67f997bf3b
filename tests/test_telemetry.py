"""Serving telemetry: registry units under a deterministic clock,
exporter goldens, scheduler lifecycle metrics, and counter persistence
across a crash/restore.

The registry tests drive a fake monotonic clock so durations, bucket
placement and exporter bytes are pinned exactly; the scheduler tests run
the real smoke engine and assert the metrics agree with the scheduler's
own ground-truth attributes.
"""
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.runtime import fault
from repro.serving import ContinuousScheduler, FaultPlan, Request
from repro.serving import telemetry as telemetry_mod
from repro.serving.telemetry import (
    METRIC_CATALOG,
    PHASES,
    Telemetry,
    default_registry,
)


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _engine(cfg, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_seq", 16)
    kw.setdefault("cache_impl", "paged")
    kw.setdefault("page_size", 4)
    kw.setdefault("stochastic_kv", False)
    return serve.Engine(cfg, **kw)


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")


# --------------------------------------------------------------------------- #
# Registry units (fake clock)
# --------------------------------------------------------------------------- #
def test_counter_monotone_and_labeled_series():
    tel = Telemetry(clock=FakeClock())
    tel.counter("serve_steps_total").inc()
    tel.counter("serve_steps_total").inc(2)
    assert tel.counter_value("serve_steps_total") == 3
    tel.counter("serve_requests_total", state="finished").inc()
    tel.counter("serve_requests_total", state="rejected").inc(4)
    assert tel.counters_by_label("serve_requests_total", "state") == {
        "finished": 1, "rejected": 4}
    with pytest.raises(ValueError):
        tel.counter("serve_steps_total").inc(-1)
    assert tel.counter_value("serve_steps_total") == 3  # unchanged


def test_gauge_overwrites():
    tel = Telemetry(clock=FakeClock())
    tel.gauge("pool_free_pages").set(7)
    tel.gauge("pool_free_pages").set(2)
    assert tel.gauge_value("pool_free_pages") == 2
    assert tel.gauge_value("pool_used_pages") == 0.0  # never set


def test_histogram_bucketing_le_semantics():
    tel = Telemetry(clock=FakeClock())
    h = tel.histogram("serve_queue_wait_steps")  # catalog buckets: 1,2,4,...
    h.observe(1)  # == edge -> that edge's bucket (le semantics)
    h.observe(3)  # first edge >= 3 is 4
    h.observe(300)  # beyond the last edge -> +Inf overflow
    assert h.counts[0] == 1  # le=1
    assert h.counts[2] == 1  # le=4
    assert h.counts[-1] == 1  # +Inf
    assert h.count == 3 and h.sum == 304


def test_histogram_requires_catalog_or_buckets():
    tel = Telemetry(clock=FakeClock())
    with pytest.raises(ValueError):
        tel.histogram("not_in_catalog_seconds")
    h = tel.histogram("not_in_catalog_seconds", buckets=(1.0, 2.0))
    h.observe(1.5)
    assert h.count == 1
    with pytest.raises(ValueError):  # unsorted edges refused
        tel.histogram("bad_edges", buckets=(2.0, 1.0))


def test_span_nesting_durations_and_trace_events():
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    with tel.span("prefill", n=2):
        clock.advance(0.1)
        with tel.span("kv_write"):
            clock.advance(0.05)
        clock.advance(0.1)
    # inner span closes first
    inner, outer = tel.events
    assert inner["name"] == "kv_write" and outer["name"] == "prefill"
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["ts"] == pytest.approx(0.1e6)
    assert inner["dur"] == pytest.approx(0.05e6)
    assert outer["ts"] == pytest.approx(0.0)
    assert outer["dur"] == pytest.approx(0.25e6)
    assert outer["args"] == {"n": "2"}
    # containment: the inner event nests inside the outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # both spans fed the phase histogram
    assert tel.histogram("serve_phase_seconds", phase="prefill").sum == \
        pytest.approx(0.25)
    assert tel.histogram("serve_phase_seconds", phase="kv_write").sum == \
        pytest.approx(0.05)


def test_instant_event_and_trace_cap(monkeypatch):
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    clock.advance(1.0)
    tel.event("chaos/killed", step=12)
    (ev,) = tel.events
    assert ev["ph"] == "i" and ev["ts"] == pytest.approx(1e6)
    assert ev["args"] == {"step": "12"}
    monkeypatch.setattr(telemetry_mod, "_MAX_EVENTS", 1)
    with tel.span("decode"):
        pass
    tel.event("chaos/overrun")
    assert len(tel.events) == 1  # nothing past the cap
    assert tel.counter_value("trace_events_dropped_total") == 2
    # spans past the cap still feed the histograms
    assert tel.histogram("serve_phase_seconds", phase="decode").count == 1


def test_phase_seconds_fixed_schema():
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    assert set(tel.phase_seconds()) == set(PHASES)  # zeroed, all present
    with tel.span("decode"):
        clock.advance(0.5)
    with tel.span("decode"):
        clock.advance(0.1)
    ph = tel.phase_seconds()
    assert ph["decode"] == {"sum_s": pytest.approx(0.6), "count": 2,
                            "mean_s": pytest.approx(0.3)}
    assert ph["kv_write"] == {"sum_s": 0.0, "count": 0, "mean_s": 0.0}


# --------------------------------------------------------------------------- #
# Exporters
# --------------------------------------------------------------------------- #
def _golden_registry():
    tel = Telemetry(clock=FakeClock())
    tel.counter("serve_steps_total").inc(3)
    tel.counter("serve_requests_total", state="finished").inc(2)
    tel.gauge("pool_free_pages").set(5)
    h = tel.histogram("serve_queue_wait_steps")
    h.observe(1)
    h.observe(3)
    h.observe(300)
    return tel


GOLDEN_PROMETHEUS = """\
# HELP pool_free_pages Free-list depth (allocatable pages).
# TYPE pool_free_pages gauge
pool_free_pages 5
# HELP serve_queue_wait_steps Steps between arrival and slot admission.
# TYPE serve_queue_wait_steps histogram
serve_queue_wait_steps_bucket{le="1"} 1
serve_queue_wait_steps_bucket{le="2"} 1
serve_queue_wait_steps_bucket{le="4"} 2
serve_queue_wait_steps_bucket{le="8"} 2
serve_queue_wait_steps_bucket{le="16"} 2
serve_queue_wait_steps_bucket{le="32"} 2
serve_queue_wait_steps_bucket{le="64"} 2
serve_queue_wait_steps_bucket{le="128"} 2
serve_queue_wait_steps_bucket{le="256"} 2
serve_queue_wait_steps_bucket{le="+Inf"} 3
serve_queue_wait_steps_sum 304
serve_queue_wait_steps_count 3
# HELP serve_requests_total Requests reaching a terminal state, by state.
# TYPE serve_requests_total counter
serve_requests_total{state="finished"} 2
# HELP serve_steps_total Engine steps executed by the scheduler.
# TYPE serve_steps_total counter
serve_steps_total 3
"""


def test_prometheus_exposition_golden(tmp_path):
    tel = _golden_registry()
    assert tel.to_prometheus() == GOLDEN_PROMETHEUS
    out = tmp_path / "sub" / "metrics.prom"  # writer creates the dir
    tel.write_prometheus(str(out))
    assert out.read_text() == GOLDEN_PROMETHEUS


def test_prometheus_label_escaping():
    tel = Telemetry(clock=FakeClock())
    tel.gauge("autotune_block_us", kernel="matmul",
              site='a"b\\c\nd', config="128x128", source="cached").set(-1)
    text = tel.to_prometheus()
    assert 'site="a\\"b\\\\c\\nd"' in text


def test_chrome_trace_json_roundtrip(tmp_path):
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    with tel.span("admit"):
        clock.advance(0.001)
    tel.event("chaos/storm", victims=2)
    trace = tel.to_chrome_trace()
    assert trace == json.loads(json.dumps(trace))  # JSON-clean
    out = tmp_path / "trace.json"
    tel.write_chrome_trace(str(out))
    loaded = json.loads(out.read_text())
    assert loaded == trace
    assert [e["name"] for e in loaded["traceEvents"]] == \
        ["admit", "chaos/storm"]
    assert loaded["displayTimeUnit"] == "ms"


def test_state_dict_roundtrip_drops_gauges():
    clock = FakeClock()
    tel = _golden_registry()
    with Telemetry(clock=clock).span("decode"):
        pass
    state = tel.state_dict()
    assert state == json.loads(json.dumps(state))  # snapshot-serializable
    tel2 = Telemetry(clock=FakeClock())
    tel2.load_state_dict(state)
    assert tel2.counter_value("serve_steps_total") == 3
    assert tel2.counter_value("serve_requests_total", state="finished") == 2
    h = tel2.histogram("serve_queue_wait_steps")
    assert h.count == 3 and h.sum == 304 and h.counts[-1] == 1
    assert tel2.gauge_value("pool_free_pages") == 0.0  # gauges not carried
    # exposition of the carried series matches the original's
    assert [ln for ln in tel2.to_prometheus().splitlines()
            if not ln.startswith("pool_free_pages") and "pool" not in ln] == \
        [ln for ln in tel.to_prometheus().splitlines()
         if not ln.startswith("pool_free_pages") and "pool" not in ln]


def test_default_registry_autotune_gauge():
    from repro.serving.telemetry import record_autotune

    record_autotune("matmul", "test-site", "128x128x128", 42.5, "measured")
    assert default_registry().gauge_value(
        "autotune_block_us", kernel="matmul", site="test-site",
        config="128x128x128", source="measured") == 42.5
    assert "autotune_block_us" in default_registry().to_prometheus()


def test_metric_catalog_names_unique_and_well_formed():
    names = [s.name for s in METRIC_CATALOG]
    assert len(names) == len(set(names))
    for s in METRIC_CATALOG:
        assert s.kind in ("counter", "gauge", "histogram"), s.name
        if s.kind == "histogram":
            assert s.buckets, s.name
            assert list(s.buckets) == sorted(set(s.buckets)), s.name
        if s.kind == "counter":
            assert s.name.endswith("_total") or s.name.endswith("_steps"), \
                s.name


# --------------------------------------------------------------------------- #
# Step, wait/fetch and compile spans
# --------------------------------------------------------------------------- #
class _FakeLogits:
    """Device logits stand-in: blocking takes 0.1 s of the fake clock,
    the copy to the host 0.02 s."""

    def __init__(self, clock):
        self.clock = clock

    def block_until_ready(self):
        self.clock.advance(0.1)
        return self

    def __array__(self, dtype=None, copy=None):
        self.clock.advance(0.02)
        return np.zeros((2, 8), np.float32)


def test_step_sync_wait_fetch_nesting():
    import types

    clock = FakeClock()
    tel = Telemetry(clock=clock)
    eng = types.SimpleNamespace(tel=tel, _tp=1)
    with tel.span("step", n=7) as step:
        with tel.span("admit"):
            clock.advance(0.001)
        step["T"] = 1
        out = serve.Engine.sync_logits(eng, _FakeLogits(clock))
    assert out.shape == (2, 8)
    ev = {e["name"]: e for e in tel.events}
    # children close first; the step span closes last
    assert [e["name"] for e in tel.events] == ["admit", "wait", "fetch",
                                               "sync", "step"]
    assert ev["wait"]["dur"] == pytest.approx(100_000.0)
    assert ev["fetch"]["dur"] == pytest.approx(20_000.0)
    assert ev["sync"]["dur"] == pytest.approx(120_000.0)
    assert ev["step"]["dur"] == pytest.approx(121_000.0)
    assert ev["step"]["args"] == {"n": "7", "T": "1"}
    # containment: wait and fetch inside sync, everything inside step
    for inner, outer in (("wait", "sync"), ("fetch", "sync"),
                         ("sync", "step"), ("admit", "step")):
        i, o = ev[inner], ev[outer]
        assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert ev["fetch"]["ts"] == pytest.approx(
        ev["wait"]["ts"] + ev["wait"]["dur"])
    # on a mesh the outer span is "collectives"
    tel2 = Telemetry(clock=clock)
    serve.Engine.sync_logits(types.SimpleNamespace(tel=tel2, _tp=2),
                             _FakeLogits(clock))
    assert [e["name"] for e in tel2.events] == ["wait", "fetch",
                                                "collectives"]


def test_step_span_opens_a_step_trace_annotation(monkeypatch):
    import jax.profiler

    opened = []

    class Annotation:
        def __init__(self, kind, name, **kw):
            self.rec = (kind, name, kw)

        def __enter__(self):
            opened.append(("enter",) + self.rec)

        def __exit__(self, *exc):
            opened.append(("exit",) + self.rec)

    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        lambda name, **kw: Annotation("step", name, **kw))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: Annotation("plain", name, **kw))
    tel = Telemetry(clock=FakeClock(), profile=True)
    with tel.span("step", n=3):
        with tel.span("wait"):
            pass
    assert opened == [("enter", "step", "step", {"step_num": 3}),
                      ("enter", "plain", "wait", {}),
                      ("exit", "plain", "wait", {}),
                      ("exit", "step", "step", {"step_num": 3})]
    # without profile=True no annotation is opened
    opened.clear()
    with Telemetry(clock=FakeClock()).span("step", n=4):
        pass
    assert opened == []


def test_fresh_jit_yields_one_compile_span_and_a_second_call_none():
    import jax
    import jax.numpy as jnp

    def fresh_for_the_compile_span_test(x):
        return x * 3.0 + 1.0

    fun = "jit(fresh_for_the_compile_span_test)"
    reg = default_registry()
    f = jax.jit(fresh_for_the_compile_span_test)
    x = jnp.arange(5.0)

    def compiles():
        return [e for e in reg.events if e["name"] == "compile"
                and e["args"]["fun"] == fun]

    assert compiles() == []
    f(x).block_until_ready()
    spans = compiles()
    assert len(spans) == 1
    assert spans[0]["args"]["source"] == "backend"
    assert spans[0]["dur"] > 0
    assert reg.counter_value("jit_compiles_total", fun=fun) == 1
    f(x).block_until_ready()
    assert len(compiles()) == 1
    assert reg.counter_value("jit_compiles_total", fun=fun) == 1


def test_compile_listener_back_dates_spans_and_marks_cache_reads(
        monkeypatch):
    clock = FakeClock(50.0)
    reg = Telemetry(clock=clock)
    reg.t0_wall_ns = 1_000_000_000_000  # the wall clock at t=50.0
    monkeypatch.setattr(telemetry_mod, "_DEFAULT", reg)
    # a backend compile from wall 1002.0 s to 1005.5 s
    telemetry_mod._on_time_span(telemetry_mod._COMPILE, 1002.0, 1005.5,
                                fun_name="jit(a)")
    # a cache read: the hit and its read time come inside the span
    telemetry_mod._on_event(telemetry_mod._CACHE_HIT)
    telemetry_mod._on_duration(telemetry_mod._CACHE_READ, 0.25)
    telemetry_mod._on_time_span(telemetry_mod._COMPILE, 1010.0, 1010.5,
                                fun_name="jit(b)")
    telemetry_mod._on_event(telemetry_mod._CACHE_MISS)
    telemetry_mod._on_time_span("/jax/other", 1.0, 2.0)  # not a compile
    a, b = reg.events
    assert (a["name"], a["ts"], a["dur"]) == ("compile", 2_000_000.0,
                                              3_500_000.0)
    assert a["args"] == {"fun": "jit(a)", "source": "backend"}
    assert (b["ts"], b["dur"]) == (10_000_000.0, 500_000.0)
    assert b["args"] == {"fun": "jit(b)", "source": "cache",
                         "cache_read_s": "0.25"}
    assert reg.counter_value("jit_compiles_total", fun="jit(a)") == 1
    assert reg.counter_value("jit_compiles_total", fun="jit(b)") == 0
    assert reg.counter_value("compile_cache_hits_total") == 1
    assert reg.counter_value("compile_cache_misses_total") == 1
    assert reg.histogram("serve_phase_seconds", phase="compile").count == 2


def test_chrome_trace_carries_the_wall_clock_anchor():
    before = __import__("time").time_ns()
    tel = Telemetry(clock=FakeClock())
    other = tel.to_chrome_trace()["otherData"]
    assert other["t0_wall_ns"] == tel.t0_wall_ns
    assert before <= tel.t0_wall_ns <= __import__("time").time_ns()


# --------------------------------------------------------------------------- #
# Scheduler lifecycle metrics (real engine, smoke scale)
# --------------------------------------------------------------------------- #
def test_scheduler_lifecycle_metrics(cfg):
    """4 requests through 2 slots: queue-wait, TTFT, inter-token and
    terminal-state metrics agree with the scheduler's own accounting."""
    rng = np.random.default_rng(3)
    queue = [rng.integers(0, cfg.vocab, size=5) for _ in range(4)]
    eng = _engine(cfg, slots=2)
    sched = ContinuousScheduler(eng, chunk=4)
    for i, p in enumerate(queue):
        sched.add(Request(rid=i, prompt=p.copy(), gen=4))
    out = sched.run()
    tel = sched.tel
    assert tel is eng.tel  # one registry for engine spans + lifecycle
    assert tel.counter_value("serve_steps_total") == sched.steps
    assert tel.counter_value("serve_decoded_tokens_total") == \
        sched.decoded_tokens
    assert tel.counter_value("serve_prefill_tokens_total") == \
        sched.prefill_tokens
    assert tel.counters_by_label("serve_requests_total", "state") == \
        {"finished": 4}
    assert tel.histogram("serve_queue_wait_steps").count == 4
    assert tel.histogram("serve_ttft_seconds").count == 4
    # gen=4 -> 3 inter-token gaps per request
    assert tel.histogram("serve_intertoken_seconds").count == \
        sum(len(v) - 1 for v in out.values())
    # 4 requests into 2 slots: somebody queued
    assert tel.histogram("serve_queue_wait_steps").sum > 0
    traces = sched.request_traces()
    assert [t["rid"] for t in traces] == [0, 1, 2, 3]
    for t in traces:
        assert t["state"] == "finished" and t["tokens_out"] == 4
        assert t["arrival_step"] <= t["admitted_step"] < t["first_token_step"]
        assert t["queue_wait_steps"] == t["admitted_step"] - t["arrival_step"]
        assert t["ttft_steps"] >= 1 and t["ttft_s"] >= 0
        assert t["prefill_charged_tokens"] == t["prompt_tokens"]  # no prefix
    assert sum(1 for t in traces if t["queue_wait_steps"] > 0) >= 1
    # pool gauges published on the last step; everything released by drain
    assert tel.gauge_value("pool_free_pages") == eng.pool.free_pages
    assert tel.gauge_value("pool_used_pages") == 0


def test_preemption_and_pool_metrics(cfg):
    """A tight pool forces spill/restore cycles; the telemetry counters
    mirror the scheduler's and the pool's ground truth."""
    rng = np.random.default_rng(8)
    queue = [rng.integers(0, cfg.vocab, size=6) for _ in range(4)]
    eng = _engine(cfg, slots=3, num_pages=7)
    sched = ContinuousScheduler(eng, chunk=4)
    for i, p in enumerate(queue):
        sched.add(Request(rid=i, prompt=p.copy(), gen=6))
    sched.run()
    tel = sched.tel
    assert sched.preemptions > 0
    assert tel.counter_value("serve_preemptions_total") == sched.preemptions
    assert tel.counter_value("serve_restores_total") == sched.restores > 0
    assert tel.counter_value("pool_spills_total") == eng.pool.spills > 0
    assert tel.counter_value("pool_restores_total") == eng.pool.restores > 0
    assert max(t["preemptions"] for t in sched.request_traces()) > 0


def test_serve_stats_decode_split_and_phases(cfg):
    """Both schedulers report decode-only vs end-to-end throughput and
    the fixed-schema phase rollup."""
    rng = np.random.default_rng(5)
    queue = [rng.integers(0, cfg.vocab, size=4) for _ in range(2)]
    for scheduler in ("continuous", "bucketed"):
        eng = _engine(cfg, slots=2)
        _, stats = serve.run(eng, [q.copy() for q in queue], gen=3,
                             quiet=True, scheduler=scheduler)
        assert stats["decode_tok_s"] > 0
        assert stats["decode_wall_s"] > 0
        assert set(stats["phases"]) >= set(PHASES)
        assert stats["phases"]["decode"]["count"] > 0
        assert stats["phases"]["prefill"]["count"] > 0
        assert stats["telemetry"] is eng.tel
        if scheduler == "continuous":
            assert all(t["state"] == "finished" for t in stats["requests"])


def test_counters_survive_kill_and_restore(cfg, tmp_path):
    """Crash recovery reports cumulative truth: after a kill + snapshot
    restore, the decoded-token and step counters match the uninterrupted
    run (the snapshot carries the registry; the replayed steps re-count
    exactly what the lost steps counted)."""
    rng = np.random.default_rng(9)
    queue = [rng.integers(0, cfg.vocab, size=6) for _ in range(4)]

    def make_engine():
        return _engine(cfg, slots=2)

    base, base_stats = fault.run_serving(make_engine, queue, gen=6,
                                         log=lambda *a: None)
    out, stats = fault.run_serving(
        make_engine, queue, gen=6, log=lambda *a: None,
        chaos=FaultPlan(kill_at_step=7),
        ckpt_dir=tmp_path / "ck", snapshot_every=3,
    )
    assert out == base and stats["restarts"] == 1
    tel, base_tel = stats["telemetry"], base_stats["telemetry"]
    assert tel.counter_value("fault_restarts_total") == 1
    assert tel.counter_value("snapshot_restores_total") == 1
    assert tel.counter_value("snapshot_saves_total") >= 2
    assert tel.counter_value("chaos_faults_total", kind="killed") == 1
    assert tel.histogram("snapshot_restore_seconds").count == 1
    assert tel.counter_value("serve_decoded_tokens_total") == \
        base_tel.counter_value("serve_decoded_tokens_total")
    assert tel.counters_by_label("serve_requests_total", "state") == \
        base_tel.counters_by_label("serve_requests_total", "state")
    # lifecycle fields survived the request-record round trip
    for t in stats["requests"]:
        assert t["state"] == "finished"
        assert t["admitted_step"] >= 0 and t["first_token_step"] >= 0
