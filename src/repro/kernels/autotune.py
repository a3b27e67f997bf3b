"""Block-size autotuner for the Pallas kernels, with a persistent cache.

Every kernel entry point (``lns_matmul``, ``fp8_elementwise``,
``flash_attention``) asks this module for its tiling when the caller does
not pin one.  Answers come from, in order:

  1. the on-disk cache (one JSON file, keyed by kernel kind, backend,
     **device model** (``jax.devices()[0].device_kind`` — a tiling
     measured on a v5e must not be replayed on a v4 sharing the cache
     file), problem shape, format, impl and mode; entries written before
     the device-kind field existed are read only where measurement is
     impossible — on a measurable backend they are ignored and the config
     is re-measured under the device-kind key),
  2. live measurement over a candidate grid — only when the backend can
     actually run compiled Pallas (TPU/GPU) or when forced,
  3. shape-aware heuristic defaults (always used in interpret mode, i.e.
     the CPU correctness path, where timings would be meaningless for the
     accelerator).

Knobs (environment):

  REPRO_AUTOTUNE        "0" never measure; "1"/"force" measure even in
                        interpret mode; unset = measure on TPU/GPU only.
  REPRO_AUTOTUNE_CACHE  cache file path (default
                        ``<checkout>/.cache/autotune.json``, beside the JAX
                        compilation cache — see ``repro.caches``).

The cache write is atomic (tmp file + rename) so concurrent processes at
worst re-measure; measurement happens with explicit blocks, so the tuner
never recurses into itself.

Every answered query also publishes an ``autotune_block_us`` gauge
(labels: kernel, site, config, source=measured|cached|heuristic) into the
process-global telemetry registry, so the serve CLI's ``--metrics-out``
exposition records which tilings this process actually ran with.  A
candidate the compiler refuses is skipped with its reason published (a
warning and ``autotune_refused_total``); when the compiler refuses every
candidate the tuner raises — it never answers with a configuration that
has not run.
"""
from __future__ import annotations

import json
import os
import pathlib
import tempfile
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

_LOCK = threading.Lock()
_CACHE: Optional[Dict[str, list]] = None

# VMEM ceiling for candidate filtering (bytes); conservative vs 16 MiB/core.
_VMEM_BUDGET = 8 * 1024 * 1024

# The paged kernel's (pages per walked block, slots per program), fixed: on
# a TPU v5 lite every (1, 2, 4, 8) x (1, 2, 4) candidate of the live-page
# walk timed within 2 % of the others at decode-long's geometry.
PAGED_BLOCKS = (4, 1)

# What a compiler refusal raises: Pallas lowering (NotImplementedError for
# a primitive with no TPU lowering, ValueError for an illegal block shape)
# and XLA/Mosaic compilation (JaxRuntimeError, VMEM exhaustion included).
COMPILE_REFUSALS = (NotImplementedError, ValueError, jax.errors.JaxRuntimeError)


def cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env).expanduser()
    from ..caches import CACHE_ROOT

    return CACHE_ROOT / "autotune.json"


def _load() -> Dict[str, list]:
    global _CACHE
    with _LOCK:
        if _CACHE is None:
            try:
                _CACHE = json.loads(cache_path().read_text())
            except (OSError, ValueError):
                _CACHE = {}
        return _CACHE


def _store(key: str, value) -> None:
    cache = _load()
    with _LOCK:
        cache[key] = list(value) if isinstance(value, (tuple, list)) else value
        path = cache_path()
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(cache, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # cache is an optimization; never fail the op over it


def clear_memory_cache() -> None:
    """Drop the in-process view (tests; external edits to the cache file)."""
    global _CACHE
    with _LOCK:
        _CACHE = None


def _device_kind() -> str:
    """Sanitized device model for cache keys (e.g. ``TPU_v5_lite``)."""
    kind = str(jax.devices()[0].device_kind)
    return kind.strip().replace("|", "/").replace(" ", "_")


def _lookup(key: str, legacy_key: str, interpret: bool):
    """Cached entry under the device-kind key.  Entries in the
    pre-device-kind key format are consulted ONLY when live measurement
    is impossible (interpret mode / measurement off) — there a legacy
    entry beats a blind heuristic.  On a measurable backend a legacy hit
    is ignored so the config gets re-measured on THIS device model and
    stored under the device-kind key; replaying it would be exactly the
    cross-device contamination the key change exists to prevent."""
    cache = _load()
    hit = cache.get(key)
    if hit is not None or _should_measure(interpret):
        return hit
    return cache.get(legacy_key)


def _fmt_config(config) -> str:
    if isinstance(config, (tuple, list)):
        return "x".join(str(c) for c in config)
    return str(config)


def _publish(kernel: str, site: str, config, best_s: Optional[float],
             source: str) -> None:
    """Mirror one tuning decision into the process-global telemetry
    registry as ``autotune_block_us{kernel, site, config, source}``.

    ``best_s=None`` (cached / heuristic answers, where nothing was timed
    in this process) publishes the sentinel -1.0."""
    from ..serving.telemetry import record_autotune

    record_autotune(kernel, site, _fmt_config(config),
                    -1.0 if best_s is None else best_s * 1e6, source)


def _should_measure(interpret: bool) -> bool:
    env = os.environ.get("REPRO_AUTOTUNE", "").lower()
    if env in ("0", "off", "never"):
        return False
    measure = env in ("1", "force", "always") or (
        not interpret and jax.default_backend() in ("tpu", "gpu"))
    if measure:
        # importing the telemetry registers its compile listener, so the
        # measurement's compiles are recorded as compile spans
        from ..serving import telemetry  # noqa: F401
    return measure


def _time_call(fn, n: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n


def _refused(kernel: str, site: str, config, err: BaseException) -> str:
    """Publish one compiler refusal; returns its one-line reason."""
    lines = str(err).strip().splitlines()
    reason = f"{type(err).__name__}: {lines[0][:200] if lines else ''}"
    warnings.warn(f"autotune: {kernel} candidate {_fmt_config(config)} at "
                  f"{site} refused by the compiler: {reason}", stacklevel=3)
    from ..serving.telemetry import record_autotune_refusal

    record_autotune_refusal(kernel, site, _fmt_config(config), reason)
    return f"{_fmt_config(config)}: {reason}"


def _measure_best(key: str, candidates: Sequence, make_fn, *, kernel: str,
                  site: str):
    """Time each candidate, cache and return the fastest (first on tie).

    The first call of a candidate compiles it.  A candidate the compiler
    refuses is skipped with its reason published (:func:`_refused`); when
    every candidate is refused this raises, so nothing unmeasured is ever
    returned or cached."""
    best, best_t, refusals = None, float("inf"), []
    for cand in candidates:
        fn = make_fn(cand)
        try:
            jax.block_until_ready(fn())
        except COMPILE_REFUSALS as err:
            refusals.append(_refused(kernel, site, cand, err))
            continue
        t = _time_call(fn, warmup=1)
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(
            f"autotune: the compiler refused every {kernel} candidate at "
            f"{site}: " + "; ".join(refusals))
    _publish(kernel, site, best, best_t, "measured")
    _store(key, best)
    return best


# --------------------------------------------------------------------------- #
# Matmul (lns / lns_loop / fused_dequant)
# --------------------------------------------------------------------------- #
def _matmul_candidates(M: int, N: int, K: int, impl: str) -> List[tuple]:
    out: List[tuple] = []
    for bm in (128, 256):
        for bn in (128, 256):
            for bk in (128, 256, 512):
                if bm > M or bn > N or bk > K:
                    continue
                cks = (8, 16, 32) if impl == "lns" else (0,)
                for ck in cks:
                    # x + w code tiles, f32 out tile, ~6 [bm, ck, bn] i32/f32
                    # temporaries for the chunked combine
                    vmem = bm * bk + bk * bn + 4 * bm * bn + 24 * bm * ck * bn
                    if vmem > _VMEM_BUDGET:
                        continue
                    out.append((bm, bn, bk, ck) if ck else (bm, bn, bk))
    return out or [_matmul_default(M, N, K, impl)]


def _matmul_default(M: int, N: int, K: int, impl: str,
                    interpret: bool = False) -> tuple:
    bm = min(128, M)
    bn = min(128, N)
    bk = min(128, K)
    if impl == "lns":
        # Interpret mode (CPU correctness/bench path) has no VMEM ceiling and
        # favors the widest chunks; compiled TPU tiles must keep the
        # [bm, ck, bn] temporaries a small slice of VMEM.
        return (bm, bn, bk, 64 if interpret else 16)
    return (bm, bn, bk)


def matmul_blocks(
    M: int, N: int, K: int, *, fmt: str, impl: str, mode: str = "rne",
    interpret: bool = False,
) -> tuple:
    """(bm, bn, bk[, ck]) tiling for ``lns_matmul`` at this problem shape,
    clamped/normalized so callers can use it directly."""
    from .lns_matmul import normalize_blocks

    def _norm(blocks):
        blocks = normalize_blocks(tuple(blocks), M, N, K)
        return blocks if impl == "lns" else blocks[:3]

    backend = jax.default_backend()
    tail = f"i{int(interpret)}|{M}x{N}x{K}|{fmt}|{impl}|{mode}"
    key = f"matmul|{backend}|{_device_kind()}|{tail}"
    cached = _lookup(key, f"matmul|{backend}|{tail}", interpret)
    if cached is not None:
        blocks = _norm(cached)
        _publish("matmul", tail, blocks, None, "cached")
        return blocks
    if not _should_measure(interpret):
        blocks = _norm(_matmul_default(M, N, K, impl, interpret))
        _publish("matmul", tail, blocks, None, "heuristic")
        return blocks

    from .lns_matmul import lns_matmul

    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.integers(0, 256, size=(M, K)).astype(np.uint8))
    w = jax.numpy.asarray(rng.integers(0, 256, size=(K, N)).astype(np.uint8))

    def make_fn(blocks):
        return lambda: lns_matmul(x, w, fmt=fmt, mode=mode, impl=impl,
                                  blocks=blocks, interpret=interpret)

    return _norm(_measure_best(key, _matmul_candidates(M, N, K, impl), make_fn,
                               kernel="matmul", site=tail))


def choose_matmul_impl(
    M: int, N: int, K: int, *, fmt: str, w_fmt: Optional[str] = None,
    mode: str = "rne", interpret: bool = False,
) -> str:
    """Resolve impl="auto": measured lns vs fused_dequant on accelerators,
    XLA dequant on CPU (where Pallas only interprets)."""
    env = os.environ.get("REPRO_MATMUL_IMPL")
    if env:
        return env
    backend = jax.default_backend()
    if backend == "cpu":
        return "xla"
    mixed = w_fmt is not None and w_fmt != fmt
    if mixed:
        return "fused_dequant"  # the LNS product is single-format
    tail = f"i{int(interpret)}|{M}x{N}x{K}|{fmt}|{mode}"
    key = f"impl|{backend}|{_device_kind()}|{tail}"
    cached = _lookup(key, f"impl|{backend}|{tail}", interpret)
    if cached is not None:
        _publish("matmul_impl", tail, cached, None, "cached")
        return cached
    if not _should_measure(interpret):
        # MXU path: the safe default on accelerators
        _publish("matmul_impl", tail, "fused_dequant", None, "heuristic")
        return "fused_dequant"

    from .lns_matmul import lns_matmul

    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.integers(0, 256, size=(M, K)).astype(np.uint8))
    w = jax.numpy.asarray(rng.integers(0, 256, size=(K, N)).astype(np.uint8))

    def make_fn(impl):
        return lambda: lns_matmul(x, w, fmt=fmt, mode=mode, impl=impl,
                                  interpret=interpret)

    return _measure_best(key, ("fused_dequant", "lns"), make_fn,
                         kernel="matmul_impl", site=tail)


# --------------------------------------------------------------------------- #
# Elementwise (fp8_elementwise)
# --------------------------------------------------------------------------- #
def _elementwise_candidates(rows: int) -> List[int]:
    return [r for r in (64, 128, 256, 512, 1024) if r <= max(rows, 64)]


def elementwise_block_rows(
    n_elements: int, *, fmt: str, op: str, mode: str = "rne",
    interpret: bool = False,
) -> int:
    """Row-block size for the (rows, 128)-tiled elementwise kernel."""
    rows = -(-n_elements // 128)
    backend = jax.default_backend()
    tail = f"i{int(interpret)}|r{rows}|{fmt}|{op}|{mode}"
    key = f"elemwise|{backend}|{_device_kind()}|{tail}"
    cached = _lookup(key, f"elemwise|{backend}|{tail}", interpret)
    if cached is not None:
        _publish("elemwise", tail, int(cached), None, "cached")
        return int(cached)
    if not _should_measure(interpret):
        _publish("elemwise", tail, 256, None, "heuristic")
        return 256

    from .fp8_elementwise import fp8_elementwise

    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.integers(0, 128, size=n_elements).astype(np.uint8))
    y = jax.numpy.asarray(rng.integers(0, 128, size=n_elements).astype(np.uint8))
    binary = op in ("mul", "div")

    def make_fn(block_rows):
        return lambda: fp8_elementwise(op, x, y if binary else None, fmt=fmt,
                                       mode=mode, block_rows=block_rows,
                                       interpret=interpret)

    best = _measure_best(key, _elementwise_candidates(rows), make_fn,
                         kernel="elemwise", site=tail)
    return int(best) if not isinstance(best, tuple) else int(best[0])


# --------------------------------------------------------------------------- #
# Flash attention
# --------------------------------------------------------------------------- #
def flash_blocks(
    Sq: int, Sk: int, hd: int, dv: int, *, interpret: bool = False,
) -> Tuple[int, int]:
    """(bq, bk) tiling for ``flash_attention``."""
    backend = jax.default_backend()
    tail = f"i{int(interpret)}|{Sq}x{Sk}x{hd}x{dv}"
    key = f"flash|{backend}|{_device_kind()}|{tail}"
    cached = _lookup(key, f"flash|{backend}|{tail}", interpret)
    if cached is not None:
        _publish("flash", tail, tuple(cached), None, "cached")
        return tuple(cached)
    # mirror the kernel's historical guard: shrink to the sequence length
    # only when it is itself sublane-aligned, otherwise keep 128 + padding
    default = (min(128, Sq) if Sq % 8 == 0 else 128,
               min(128, Sk) if Sk % 8 == 0 else 128)
    if not _should_measure(interpret):
        _publish("flash", tail, default, None, "heuristic")
        return default

    from .flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jax.numpy.asarray(rng.standard_normal((1, Sq, 4, hd)).astype(np.float32))
    k = jax.numpy.asarray(rng.standard_normal((1, Sk, 4, hd)).astype(np.float32))
    v = jax.numpy.asarray(rng.standard_normal((1, Sk, 4, dv)).astype(np.float32))
    candidates = [(bq, bk) for bq in (64, 128, 256) for bk in (64, 128, 256)
                  if bq <= Sq and bk <= Sk] or [default]

    def make_fn(cand):
        bq, bk = cand
        return lambda: flash_attention(q, k, v, bq=bq, bk=bk, interpret=interpret)

    return tuple(_measure_best(key, candidates, make_fn,
                               kernel="flash", site=tail))


# --------------------------------------------------------------------------- #
# Paged decode attention (and the mixed prefill+decode step, which runs the
# same per-sub-step paged attention — ``site`` keys the two separately)
# --------------------------------------------------------------------------- #
def paged_blocks(
    B: int, maxp: int, page: int, KV: int, G: int, hd: int, *,
    fmt: str, interpret: bool = False, site: str = "",
) -> Tuple[int, int]:
    """(pages_per_block, slots_per_block) for the paged-attention kernel.

    A kernel program walks the live pages of ``slots_per_block`` slots,
    ``pages_per_block`` pages per gathered block.  The answer is
    :data:`PAGED_BLOCKS` for every shape, never measured: the arguments
    only name the decision in the telemetry (``site``: "decode" vs
    "mixed" call sites).
    """
    tail = (f"i{int(interpret)}|{B}x{maxp}x{page}|kv{KV}g{G}hd{hd}|{fmt}"
            + (f"|{site}" if site else ""))
    _publish("paged", tail, PAGED_BLOCKS, None, "heuristic")
    return PAGED_BLOCKS
