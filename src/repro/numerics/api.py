"""The functional numerics API model and serving code calls.

Every entry point takes the value operands plus a :class:`Policy` (or the
legacy ``QuantConfig`` — the deprecation shim) and an optional ``site``
name, resolves ``(fmt, mode, impl, accum)`` internally, and dispatches to
the kernels.  Call sites never thread numeric strings.

``REPRO_FORCE_LEGACY_QUANTCONFIG=1`` forces model layers back onto the
preserved string-kwarg code paths driven by a ``QuantConfig`` (see
``models.layers._qlinear_legacy``); the policy-resolved paths here are
pinned bit-identical to them by ``tests/test_numerics.py``.
"""
from __future__ import annotations

import os
import warnings
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from .policy import (
    SINGLE_FORMAT_IMPLS,
    OpPolicy,
    Policy,
    from_quant_config,
)

PolicyLike = Union[Policy, Any, None]  # Policy | QuantConfig | None

_ACCUM_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def force_legacy() -> bool:
    """True when the legacy QuantConfig string-kwarg paths are forced."""
    return os.environ.get("REPRO_FORCE_LEGACY_QUANTCONFIG") == "1"


def is_legacy_config(pol: PolicyLike) -> bool:
    """Duck-typed QuantConfig detection (avoids a configs import cycle)."""
    return pol is not None and hasattr(pol, "kv_cache_fp8")


_warned_legacy = False


def as_policy(pol: PolicyLike) -> Optional[Policy]:
    """Coerce ``None | QuantConfig | Policy`` to ``None | Policy``."""
    global _warned_legacy
    if pol is None or isinstance(pol, Policy):
        return pol
    if is_legacy_config(pol):
        if not _warned_legacy and not force_legacy():
            _warned_legacy = True
            warnings.warn(
                "passing QuantConfig to the numerics API is deprecated; "
                "use QuantConfig.to_policy() or a named policy preset",
                DeprecationWarning, stacklevel=3,
            )
        return from_quant_config(pol)
    raise TypeError(f"expected Policy, QuantConfig or None, got {type(pol)}")


def _as_qtensor(w, pol: Optional[Policy]):
    """Normalize a static-quantized weight to the QTensor carrier."""
    from ..core.quant import QTensor

    if isinstance(w, QTensor):
        return w
    fmt = pol.weights.fmt if pol is not None and pol.weight_quant else "e4m3"
    return QTensor(codes=w["codes"],
                   scale=jnp.asarray(w["scale"], jnp.float32), fmt=fmt)


def is_quantized_weight(w) -> bool:
    from ..core.quant import QTensor

    return isinstance(w, QTensor) or (isinstance(w, dict) and "codes" in w)


def dequantize_weight(w, pol: PolicyLike = None, dtype=jnp.bfloat16):
    """Static-quantized weight -> compute dtype (no-op for plain arrays).

    The policy resolves the legacy dict carrier's format; the decode
    itself is ``models.quantize.resolve_weight`` (one implementation).
    """
    if not is_quantized_weight(w):
        return w
    from ..models.quantize import resolve_weight

    return resolve_weight(w, weight_format(pol), dtype)


def weight_format(pol: PolicyLike, site: str = "") -> Optional[str]:
    """The weight-side FP8 format at ``site`` (None = unquantized)."""
    if is_legacy_config(pol):
        return pol.weight_fmt
    if pol is None or not pol.weight_quant:
        return None
    return pol.resolve("weights", site).fmt


# --------------------------------------------------------------------------- #
# Matmul
# --------------------------------------------------------------------------- #
def static_matmul_2d(x2d, qw, pol: Policy, site: str = ""):
    """[M, K] float @ static QTensor weight -> f32 [M, N], codes end to
    end.  The ONE policy-resolved static matmul body — both
    :func:`matmul` and ``models.quantize.static_qmatmul`` call it, so the
    two surfaces cannot drift.
    """
    from ..core.quant import quantize
    from ..kernels import ops as kops

    mp = pol.resolve("matmul", site)
    act_fmt = mp.fmt if mp.quantized else qw.fmt
    if mp.impl in SINGLE_FORMAT_IMPLS and act_fmt != qw.fmt:
        act_fmt = qw.fmt  # the LNS product is single-format
    qx = quantize(x2d, act_fmt, mode=mp.mode)
    return kops.matmul_q(qx, qw, impl=mp.impl, mode=mp.mode,
                         compute_dtype=_ACCUM_DTYPES[mp.accum])


def matmul(x, w, pol: PolicyLike, *, site: str = "", bias=None):
    """[..., K] @ [K, N] under the policy; the one matmul entry point.

    ``w`` is a float array (training; STE-quantized when the policy says
    so) or a :class:`QTensor` (static weights: codes feed the quantized
    matmul directly, only 1 byte/param crosses HBM).  Returns [..., N] in
    ``x.dtype``.  ``impl="auto"`` defers to ``kernels.autotune`` inside
    ``kernels.ops.matmul_q``.
    """
    pol = as_policy(pol)
    shape = x.shape
    mp = pol.resolve("matmul", site) if pol is not None else OpPolicy()
    if is_quantized_weight(w):
        qw = _as_qtensor(w, pol)
        if pol is not None and mp.quantized:
            y = static_matmul_2d(x.reshape(-1, shape[-1]), qw, pol, site)
            y = y.reshape(*shape[:-1], qw.shape[-1]).astype(x.dtype)
        else:
            y = x @ dequantize_weight(qw, pol, x.dtype)
    elif pol is not None and pol.ste_weights:
        from ..models.layers import _ste_qmatmul

        wp = pol.resolve("weights", site)
        act_fmt = mp.fmt if mp.quantized else wp.fmt
        if mp.impl in SINGLE_FORMAT_IMPLS and act_fmt != wp.fmt:
            act_fmt = wp.fmt  # the LNS product is single-format
        x2d = x.reshape(-1, shape[-1])
        y = _ste_qmatmul(x2d, w, act_fmt, wp.fmt, mp.impl, mp.quantized,
                         mp.mode, mp.accum)
        y = y.reshape(*shape[:-1], w.shape[-1]).astype(x.dtype)
    else:
        y = x @ w
    return y if bias is None else y + bias


# --------------------------------------------------------------------------- #
# Elementwise
# --------------------------------------------------------------------------- #
def elementwise(op: str, x, y=None, pol: PolicyLike = None, *,
                site: str = ""):
    """Paper elementwise op (mul/div/square/recip/sqrt/rsqrt) under the
    policy: quantize -> LNS code-domain op -> dequantize, or the plain
    float op when the policy leaves elementwise in full precision.
    Returns a float array in ``x.dtype``.
    """
    pol = as_policy(pol)
    ep = pol.resolve("elementwise", site) if pol is not None else OpPolicy()
    if not ep.quantized:
        f = {
            "mul": lambda: x * y,
            "div": lambda: x / y,
            "square": lambda: x * x,
            "recip": lambda: 1.0 / x,
            "sqrt": lambda: jnp.sqrt(x),
            "rsqrt": lambda: jax.lax.rsqrt(x),
        }[op]
        return f()
    from ..core.quant import quantize
    from ..kernels import ops as kops

    qx = quantize(x, ep.fmt)
    qy = None if y is None else quantize(y, ep.fmt)
    impl = "pallas" if ep.impl == "auto" else ep.impl
    out = kops.elementwise_q(op, qx, qy, mode=ep.mode, impl=impl)
    return out.dequantize().astype(x.dtype)


# --------------------------------------------------------------------------- #
# KV cache
# --------------------------------------------------------------------------- #
def kv_quantized(pol: PolicyLike) -> bool:
    if is_legacy_config(pol):
        return bool(pol.kv_cache_fp8)
    return pol is not None and pol.kv_quantized


def kv_format(pol: PolicyLike) -> Optional[str]:
    """The KV-cache FP8 format (None = cache stays in compute dtype)."""
    if is_legacy_config(pol):
        return pol.kv_fmt if pol.kv_cache_fp8 else None
    return pol.kv_fmt if pol is not None else None


def kv_stochastic(pol: PolicyLike) -> bool:
    """Whether KV writes should use stochastic-rounding carry-ins."""
    if is_legacy_config(pol):
        return bool(pol.kv_cache_fp8)
    return (pol is not None and pol.kv_quantized
            and pol.kv_write.mode == "stochastic")


def _kv_mode(pol: Optional[Policy], op: str, has_key: bool) -> str:
    """Resolved rounding mode for a KV write/rescale.

    Stochastic rounding needs a PRNG key; without one the write falls back
    to the deterministic attention-read mode (historically
    ``QuantConfig.mode``, carried here by ``attention_qk.mode``).
    """
    mode = pol.resolve(op).mode
    if mode == "stochastic" and not has_key:
        mode = pol.resolve("attention_qk").mode
        if mode == "stochastic":
            mode = "rne"
    return mode


def kv_encode(x, pol: PolicyLike, *, key=None):
    """float K/V -> the cache representation (codes when KV is quantized,
    pass-through otherwise).  The dense-cache store path."""
    if is_legacy_config(pol):  # legacy string path: encode at config fmt
        from ..core.quant import encode

        if not pol.kv_cache_fp8:
            return x
        return encode(x.astype(jnp.float32), pol.kv_fmt)
    if pol is None or not pol.kv_quantized:
        return x
    from ..core.quant import encode

    mode = pol.resolve("kv_write").mode
    if mode == "stochastic" and key is None:
        # the dense-cache store path historically always encoded RNE when
        # no key was supplied (unlike the paged writes, whose no-key
        # fallback is the config's deterministic mode) — keep that exact
        # behavior so forced-legacy and policy runs stay bit-identical
        mode = "rne"
    return encode(x.astype(jnp.float32), pol.kv_write.fmt, mode, key=key)


def kv_decode(x, pol: PolicyLike):
    """Cache representation -> float (LUT/bit-placement decode)."""
    if not kv_quantized(pol):
        return x
    from ..kernels.common import code_to_f32

    return code_to_f32(x, kv_format(pol))


def kv_write_token(pol: PolicyLike, pages, scales, new, page_ids, rows, *,
                   key=None, write_mask=None):
    """One decode token's K or V into its page (see
    ``serving.page_pool.write_token_page``); fmt/mode resolved here.

    ``key`` may be a single PRNG key or a per-slot batch (the
    position-addressed serving streams); ``write_mask`` is the explicit
    [B] write mask — masked lanes land in the reserved null page."""
    from ..serving.page_pool import write_token_page

    if is_legacy_config(pol):
        fmt = pol.kv_fmt if pol.kv_cache_fp8 else None
        mode = "stochastic" if key is not None else pol.mode
        return write_token_page(pages, scales, new, page_ids, rows, fmt=fmt,
                                mode=mode, key=key, write_mask=write_mask)
    fmt = kv_format(pol)
    mode = "rne" if pol is None else _kv_mode(pol, "kv_write", key is not None)
    return write_token_page(pages, scales, new, page_ids, rows, fmt=fmt,
                            mode=mode, key=key, write_mask=write_mask)


def kv_write_prefill(pol: PolicyLike, pages, scales, src, page_ids, *,
                     key=None):
    """Splice a prefill cache row into pages (see
    ``serving.page_pool.write_prefill_pages``); fmt/mode resolved here."""
    from ..serving.page_pool import write_prefill_pages

    if is_legacy_config(pol):
        fmt = pol.kv_fmt if pol.kv_cache_fp8 else None
        mode = "stochastic" if key is not None else pol.mode
        return write_prefill_pages(pages, scales, src, page_ids, fmt=fmt,
                                   mode=mode, key=key)
    fmt = kv_format(pol)
    mode = ("rne" if pol is None
            else _kv_mode(pol, "kv_rescale", key is not None))
    return write_prefill_pages(pages, scales, src, page_ids, fmt=fmt,
                               mode=mode, key=key)


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #
def attention(q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
              pol: PolicyLike, *, n_kv_heads: int, window: int = 0,
              cap: float = 0.0, scale: Optional[float] = None, site: str = ""):
    """Paged decode attention under the policy.

    QK^T runs in the LNS integer domain off the page codes when the KV
    cache is quantized (``attention_qk`` resolves format/mode/impl);
    float pages take the float path.  ``scale`` is the softmax scale (None:
    the head width ** -0.5); a query wider than its k pages is MLA's (see
    ``kernels.paged_attention``).  Returns [B, 1, H, dv] in q.dtype.
    """
    from ..kernels.paged_attention import paged_decode_attention

    if is_legacy_config(pol):
        fmt = pol.kv_fmt if pol.kv_cache_fp8 else None
        return paged_decode_attention(
            q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
            fmt=fmt, n_kv_heads=n_kv_heads, mode=pol.mode, window=window,
            cap=cap, scale=scale,
        )
    qk = pol.resolve("attention_qk", site) if pol is not None else OpPolicy()
    fmt = kv_format(pol)
    mode = qk.mode if qk.mode != "stochastic" else "rne"
    impl = qk.impl if qk.impl in ("kernel", "ref", "batch") else "auto"
    return paged_decode_attention(
        q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
        fmt=fmt, n_kv_heads=n_kv_heads, mode=mode, window=window, cap=cap,
        scale=scale, impl=impl, site=site,
    )


def kv_fused_write_attend(q, k_new, v_new, k_pages, v_pages, k_scale,
                          v_scale, block_tables, lengths, pol: PolicyLike, *,
                          n_kv_heads: int, k_key=None, v_key=None,
                          write_mask=None, window: int = 0, cap: float = 0.0,
                          scale: Optional[float] = None, site: str = ""):
    """Fused decode-token KV write + paged attention, policy-resolved.

    One launch replacing the ``kv_write_token`` x2 -> ``attention``
    composition on the decode hot path; bit-identical to it on every
    active (``write_mask``) lane.  ``lengths`` are pre-write; the write
    lands at position ``lengths`` and attention spans ``lengths + 1``.
    The KV write fmt/mode resolve exactly like ``kv_write_token``, the
    QK^T fmt/mode/impl exactly like ``attention``.

    Returns ``(out, new_k_pages, new_k_scale, new_v_pages, new_v_scale)``.
    """
    from ..kernels.paged_attention import fused_decode_write_attend

    has_key = k_key is not None
    if is_legacy_config(pol):
        fmt = pol.kv_fmt if pol.kv_cache_fp8 else None
        kv_mode = "stochastic" if has_key else pol.mode
        mode, impl = pol.mode, "auto"
    else:
        fmt = kv_format(pol)
        kv_mode = ("rne" if pol is None
                   else _kv_mode(pol, "kv_write", has_key))
        qk = (pol.resolve("attention_qk", site) if pol is not None
              else OpPolicy())
        mode = qk.mode if qk.mode != "stochastic" else "rne"
        impl = qk.impl if qk.impl in ("kernel", "ref", "batch") else "auto"
    return fused_decode_write_attend(
        q, k_new, v_new, k_pages, v_pages, k_scale, v_scale, block_tables,
        lengths, fmt=fmt, n_kv_heads=n_kv_heads, mode=mode, kv_mode=kv_mode,
        k_key=k_key, v_key=v_key, write_mask=write_mask, window=window,
        cap=cap, scale=scale, impl=impl, site=site,
    )
