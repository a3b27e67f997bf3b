"""One run of one cell: set-up, the measured window, the traced slice,
the correctness check and the result line.

The window drives the program's own serving path: a
``repro.launch.serve.Engine`` (paged FP8 cache, fused write+attend
kernel) under a ``repro.serving.scheduler.ContinuousScheduler`` whose
``step()`` the harness calls itself, handing each request to ``add()``
when its wall-clock time comes due and timestamping each token through
``on_token``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import cells, costs, peaks, stats, tracing, traffic

REPO = cells.REPO
CACHE = REPO / ".cache"
TRACE_DIR = CACHE / "chipbench" / "trace"
HOST_SPANS = ("admit", "host", "sync", "prefill", "decode", "kv_write",
              "preempt", "restore", "collectives")

# ModelConfig fields the configuration's published keys set.
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings",
           "attention_bias": "qkv_bias", "qk_norm": "qk_norm"}


class NoAccelerator(SystemExit):
    pass


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_caches() -> None:
    """JAX's persistent compilation cache and the kernel autotuner's file,
    both at fixed paths inside the checkout; every program is cached, so
    only a checkout's first run of a cell compiles."""
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(CACHE / "autotune.json")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compile_log()


def require_accelerator(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoAccelerator(f"chipbench: no TPU: JAX's first device is "
                            f"{d.platform!r}; the benchmark runs only on a "
                            "TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"chipbench: the cell needs {chips} chips, JAX "
                            f"reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def import_program():
    src = REPO / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: the program (src/repro) is not in "
                         f"this checkout ({REPO})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def program_config(cell: cells.Cell):
    """The program's ModelConfig for the cell's configuration."""
    from repro.configs import get_config

    prog = cell.config["program"]
    cfg = get_config(prog["arch"], policy=prog["policy"])
    changes = {f: cell.model[k] for k, f in _FIELDS.items()
               if k in cell.model}
    return dataclasses.replace(cfg, **changes)


# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class StepRecord:
    index: int       # scheduler step number
    t_start: float   # host clock when step() was called
    T: int = 0       # tokens per lane the model step ran (0: no model call)
    lengths: Optional[np.ndarray] = None
    n_new: Optional[np.ndarray] = None
    emitted: int = 0  # tokens sampled in this step
    used_pages: int = 0  # pool pages held by requests after the step


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (host clock in seconds)."""

    cell: cells.Cell
    seed: int
    seconds: float
    dims: costs.Dims
    device_kind: str
    slots: int
    page_size: int
    pages: int  # pool pages, the reserved null page included
    setup_s: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    records: List[traffic.RequestRecord] = dataclasses.field(
        default_factory=list)
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    counters_open: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters_close: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[tracing.Trace] = None
    trace_t0: float = 0.0  # host clock of the traced slice
    trace_t1: float = 0.0
    compiles_in_window: int = 0
    states: Dict[int, str] = dataclasses.field(default_factory=dict)
    sched: object = None  # the scheduler, for tools that reuse the engine

    @property
    def peaks(self) -> peaks.Peaks:
        """The chip's published peaks (an unknown device kind raises)."""
        return peaks.peaks_for(self.device_kind)

    def window_steps(self) -> List[StepRecord]:
        return [s for s in self.steps if self.t_open <= s.t_start < self.t_close]

    def traced_steps(self) -> List[StepRecord]:
        return [s for s in self.steps
                if self.trace_t0 <= s.t_start < self.trace_t1]


_COUNTERS = ("steps", "occupied_slot_steps", "decoded_tokens",
             "prefill_tokens", "preemptions", "restores", "shed")


def _counters(sched) -> Dict[str, float]:
    return {k: float(getattr(sched, k)) for k in _COUNTERS}


# --------------------------------------------------------------------------- #
class Server:
    """The program under test, sized for one cell; :meth:`build` makes
    its weights and engine for one seed.  The page pool is the engine's
    own default: room for every slot's longest request, so no request is
    ever preempted for pages."""

    def __init__(self, cell: cells.Cell, *, profile: bool, log_fn=log):
        from repro.kernels import autotune

        s = cell.settings
        self.cell = cell
        self.profile = profile
        self.log = log_fn
        self.slots = int(s["slots"])
        self.chunk = int(s["chunk"])
        self.page_size = int(s["page_size"])
        self.max_seq = traffic.max_tokens(cell.traffic)
        self.cfg = cfg = program_config(cell)
        self.eng = None
        self.tel = None
        self.pages = 0
        # the paged kernel's block shape is measured (or read from the
        # autotune file) before the pool takes the device's memory
        t = time.perf_counter()
        blocks = autotune.paged_blocks(
            self.slots, -(-self.max_seq // self.page_size), self.page_size,
            cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd,
            fmt="e5m2", site="blocks.0.attn")
        log_fn(f"paged kernel blocks {blocks} "
               f"({time.perf_counter() - t:.1f} s)")

    def build(self, seed: int) -> None:
        """Weights from ``seed`` (one jitted call), then the engine."""
        import jax
        from unittest import mock

        from repro.launch import serve
        from repro.models import model as model_mod
        from repro.serving.telemetry import Telemetry

        self.eng = None
        gc.collect()
        ref = cells.reference(self.cell.config["reference"], self.cell.root)
        t = time.perf_counter()
        params = ref.make_params(self.cell.model, seed, self.cfg.vocab_padded)
        jax.block_until_ready(params)
        self.log(f"weights: {time.perf_counter() - t:.1f} s "
                 f"({compile_log().summary()} so far)")
        self.tel = Telemetry(clock=time.perf_counter, profile=self.profile)
        # The engine takes no weights argument: it calls Model.init once,
        # for its params; hand it the benchmark's for that call.
        with mock.patch.object(model_mod.Model, "init",
                               lambda _self, _key: params):
            self.eng = serve.Engine(
                self.cfg, slots=self.slots, max_seq=self.max_seq,
                cache_impl="paged", page_size=self.page_size,
                rng_seed=_engine_seed(seed), fused_decode=True,
                telemetry=self.tel)
        self.pages = self.eng.pool.num_pages
        self.log(f"pool: {self.pages} pages of {self.page_size} tokens")

    def warm_up(self) -> None:
        """Compile and run the two step programs the scheduler drives
        (T=chunk while a prefill is in flight, T=1 for pure decode), with
        every lane idle."""
        import jax

        B = self.slots
        zeros = np.zeros((B,), np.int32)
        for T in (self.chunk, 1, self.chunk, 1):
            out = self.eng.step_chunk(np.zeros((B, T), np.int32), zeros,
                                      zeros, sync=False)
            jax.block_until_ready(out)

    def close(self) -> None:
        self.eng = None
        gc.collect()


def _engine_seed(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed), 0xe9]).generate_state(1)[0]
               % (2 ** 31 - 64))


# --------------------------------------------------------------------------- #
class Driver:
    """Feeds requests to the scheduler on the wall clock and records what
    comes back."""

    def __init__(self, server: Server, records, clock=time.perf_counter):
        from repro.serving.scheduler import ContinuousScheduler, Request

        self.server = server
        self.records = records
        self.by_rid = {r.rid: r for r in records}
        self.clock = clock
        self._Request = Request
        self.steps: List[StepRecord] = []
        self._cur: Optional[StepRecord] = None
        self.sched = ContinuousScheduler(server.eng, chunk=server.chunk,
                                         on_token=self._on_token)
        eng = server.eng
        inner = eng.step_chunk

        def recording_step(tokens, lengths, n_new, *, sync=True):
            if self._cur is not None:
                self._cur.T = int(np.asarray(tokens).shape[1])
                self._cur.lengths = np.array(lengths, np.int64)
                self._cur.n_new = np.array(n_new, np.int64)
            return inner(tokens, lengths, n_new, sync=sync)

        eng.step_chunk = recording_step
        self.next = 0

    def _on_token(self, rid: int, tok: int, step: int) -> None:
        r = self.by_rid[rid]
        r.tokens.append(int(tok))
        r.token_times.append(self.clock())
        if self._cur is not None:
            self._cur.emitted += 1

    def add_due(self, now: float) -> None:
        while (self.next < len(self.records)
               and self.records[self.next].due <= now):
            r = self.records[self.next]
            r.added = self.clock()
            self.sched.add(self._Request(rid=r.rid, prompt=r.prompt,
                                         gen=r.gen, arrival=self.sched.steps))
            self.next += 1

    def step(self) -> None:
        self._cur = StepRecord(index=self.sched.steps, t_start=self.clock())
        self.steps.append(self._cur)
        self.sched.step()
        self._cur.used_pages = self.server.eng.pool.used_pages
        self._cur = None

    def admitted_times(self) -> None:
        t = {s.index: s.t_start for s in self.steps}
        for rid, req in self.sched.by_rid.items():
            if req.admitted_step >= 0 and req.admitted_step in t:
                self.by_rid[rid].admitted = t[req.admitted_step]

    def states(self) -> Dict[int, str]:
        return {rid: req.state for rid, req in self.sched.by_rid.items()}


# --------------------------------------------------------------------------- #
def _fill_first_wave(drv: Driver) -> None:
    """Backlog cells: admit the first wave and run until every slot holds
    a request with its first token."""
    drv.add_due(float("inf"))
    sched = drv.sched
    while True:
        live = list(sched.active.values())
        if (live and all(len(r.out) > 0 for r in live)
                and (len(live) == drv.server.slots or not sched.queued)):
            return
        drv.step()


class CompileLog:
    """Compiles and persistent-cache lookups, with their host times, for
    the whole process (set-up's report and the in-window count)."""

    def __init__(self):
        import jax

        self.compiles: List[tuple] = []  # (time, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), duration))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t < t1)

    def summary(self) -> str:
        return (f"{len(self.compiles)} compiles "
                f"({sum(d for _, d in self.compiles):.1f} s), compile cache "
                f"{self.hits} hits / {self.misses} misses")


_COMPILES: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileLog()
    return _COMPILES


def serve_window(server: Server, cell: cells.Cell, seed: int,
                 seconds: float, *, trace: bool, t_process: float,
                 log_fn=log) -> Run:
    """Set-up's last part (the load's warm-up, or a backlog's first wave),
    the window, and the traced slice.  Returns the run record."""
    import jax

    s = cell.settings
    run = Run(cell=cell, seed=seed, seconds=seconds,
              dims=costs.Dims.from_config(cell.model),
              device_kind=jax.devices()[0].device_kind,
              slots=server.slots, page_size=server.page_size,
              pages=server.pages)
    backlog = cell.traffic["arrivals"] == "backlog"
    warm = 0.0 if backlog else float(s.get("warmup_s", 0.0))
    records = traffic.generate(
        cell.traffic, seed=seed, vocab=cell.model["vocab_size"],
        seconds=seconds, warmup=warm, rate_per_s=s.get("rate_per_s"),
        requests=s.get("requests"), block=server.slots)
    compiles = compile_log()
    drv = Driver(server, records)
    clock = drv.clock
    t0 = clock()
    for r in records:
        r.due = t0 + r.offset
    if backlog:
        _fill_first_wave(drv)
        run.t_open = clock()
    else:
        run.t_open = t0 + warm
    run.setup_s = run.t_open - t_process
    log_fn(f"set-up: {compiles.summary()}")
    run.t_close = run.t_open + seconds
    # The traced slice is the window's last trace_seconds: stopping the
    # profiler holds the host for seconds (it collects the trace), and
    # that stall must fall after the window, not inside it.
    trace_len = float(s.get("trace_seconds", 5.0))
    trace_from = run.t_close - trace_len
    tracing_now = opened = False
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    annotation = None
    while True:
        now = clock()
        if now >= run.t_close:
            break
        if not opened and now >= run.t_open:
            run.counters_open = _counters(drv.sched)
            opened = True
        if trace and not tracing_now and run.trace_t0 == 0.0 \
                and now >= trace_from:
            jax.block_until_ready(server.eng.cache)
            jax.profiler.start_trace(str(TRACE_DIR))
            annotation = jax.profiler.TraceAnnotation(tracing.WINDOW)
            annotation.__enter__()
            run.trace_t0 = clock()
            tracing_now = True
        drv.add_due(now)
        if drv.sched.pending():
            drv.step()
        else:
            nxt = (records[drv.next].due if drv.next < len(records)
                   else run.t_close)
            time.sleep(max(0.0, min(nxt, run.t_close) - clock()))
    if tracing_now:
        jax.block_until_ready(server.eng.cache)
        run.trace_t1 = clock()
        annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if not opened:
        run.counters_open = _counters(drv.sched)
    run.counters_close = _counters(drv.sched)
    run.compiles_in_window = compiles.between(run.t_open, run.t_close)
    drv.admitted_times()
    run.records = records
    run.steps = drv.steps
    base = server.tel._t0  # span timestamps are relative to this clock
    run.spans = [dict(e, t=base + e["ts"] * 1e-6, d=e["dur"] * 1e-6)
                 for e in server.tel.events if e.get("ph") == "X"]
    run.states = drv.states()
    run.sched = drv.sched
    if trace:
        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        if files:
            run.trace = tracing.reduce(tracing.load_xplane(str(files[-1])),
                                       HOST_SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return run


# --------------------------------------------------------------------------- #
FAILED_STATES = ("rejected", "failed", "timed_out", "cancelled")


def check_sample(run: Run, n: int) -> List[traffic.RequestRecord]:
    """Requests whose served tokens are compared: a sample drawn from the
    seed, with the longest (prompt + served) always in it."""
    cand = [r for r in run.records if r.tokens]
    if not cand:
        return []
    longest = max(cand, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in cand if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 0xc4e]))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(run: Run, sample, *, control: bool = False):
    """Reference gaps of every served token of the sample, one array per
    request.  Returns (served gaps, control gaps or None)."""
    ref = cells.reference(run.cell.config["reference"], run.cell.root)
    longest = max(len(r.prompt) + len(r.tokens) for r in sample)
    pad = -(-longest // 512) * 512
    out = ref.served_gaps(run.cell.model, run.seed,
                          [(r.prompt, r.tokens) for r in sample],
                          pad_to=pad, control=control)
    return [o[0] for o in out], ([o[1] for o in out] if control else None)


def numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The numbers a set of per-request gaps gives: the widest gap, the
    mean gap over all tokens, and the median over requests of each
    request's mean gap."""
    every = np.concatenate(gaps)
    return {"gap_max": float(np.max(every)),
            "gap_mean": float(np.mean(every)),
            "gap_req_median": float(np.median([np.mean(g) for g in gaps]))}


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for name, read in cells.readers(metrics, run.cell.root).items():
        v = read(run)
        if v is not None:
            unit = next(m["unit"] for m in metrics if m["name"] == name)
            out[name] = {"value": float(v), "unit": unit}
    return out


def breakdown(tr: tracing.Trace) -> dict:
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps()[:10]]}


def run_cell(cell: cells.Cell, seed: int, seconds: float, *, trace: bool,
             t_process: float, device: dict):
    """Everything after the device check.  Returns (result dict, run)."""
    server = Server(cell, profile=trace)
    server.build(seed)
    server.warm_up()
    run = serve_window(server, cell, seed, seconds, trace=trace,
                       t_process=t_process)
    device = dict(device, memory_peak_bytes=memory_peak())
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)
    # the reference runs after the program's state is freed
    run.sched = None
    server.close()
    del server
    gc.collect()

    due = [r for r in run.records if run.t_open <= r.due < run.t_close]
    _, censored = stats.ttft_sample(run.records, run.t_open, run.t_close)
    gaps = stats.token_gaps(run.records, run.t_open, run.t_close)
    states = run.states
    attempted = sum(1 for r in run.records if r.added is not None)
    failed = sum(1 for st in states.values() if st in FAILED_STATES)
    log(f"requests: attempted {attempted}, due in window {len(due)}, "
        f"finished {sum(1 for st in states.values() if st == 'finished')}, "
        f"failed {failed}; TTFTs censored {censored}; token gaps "
        f"{len(gaps)}; steps in window {len(run.window_steps())}; "
        f"compiles in window {run.compiles_in_window}; pool fill "
        f"{stats.pool_fill(run.window_steps(), run.pages)}")
    sample = check_sample(run, int(cell.settings["check"]["requests"]))
    t = time.perf_counter()
    g, _ = compare(run, sample) if sample else ([], None)
    log(f"check: {len(sample)} requests, {sum(x.size for x in g)} served "
        f"tokens, reference in {time.perf_counter() - t:.1f} s")
    limits = cell.settings["check"].get("limits", {})
    nums = numbers(g) if g else {}
    for k, v in nums.items():
        if k not in limits:
            log(f"reading {k} {v!r} (not compared)")
    checks = {k: {"value": nums.get(k), "limit": lim}
              for k, lim in limits.items()}
    correct = (attempted > 0 and failed == 0 and bool(checks)
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = breakdown(run.trace)
    result["checks"] = checks
    return result, run


def main(argv=None, t_process: Optional[float] = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse_args(argv)
    cell = cells.load_cell(args.workload)
    configure_caches()
    try:
        device = require_accelerator(cell.chips)
    except NoAccelerator as e:
        log(str(e))
        return 3
    import_program()
    log(f"cell {cell.name}: {cell.config_name} under {cell.traffic_name}, "
        f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}; "
        f"{device['kind']} x{device['count']}")
    result, _ = run_cell(cell, args.seed, args.seconds,
                         trace=bool(args.trace), t_process=t_process,
                         device=device)
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
