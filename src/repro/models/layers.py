"""Model building blocks: norms, rope, attention, MLPs, quantized linear.

Everything is a pure function over parameter pytrees (dicts of jnp arrays);
no framework objects.  All shapes are static => usable under jax.eval_shape
for the 512-device dry-run.

The paper's technique enters through :func:`qlinear`: when the numerics
policy (``cfg.policy`` — see :mod:`repro.numerics`) quantizes matmuls,
every linear quantizes activations and weights to FP8 codes and multiplies
in the LNS integer domain (Pallas kernel on TPU, XLA dequant path for CPU
lowering), with a straight-through estimator for gradients (standard FP8
training recipe).  Formats, rounding modes and kernel impls are resolved
per call site from the policy; no numeric strings are threaded here.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.quant import quantize
from ..kernels import ops as kops
from ..parallel.hints import hint_meta

# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def qk_rms_norm(x, scale, eps=1e-6):
    """Per-head RMSNorm over head_dim (qwen3/gemma3 style)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# --------------------------------------------------------------------------- #
# Rotary embeddings
# --------------------------------------------------------------------------- #
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 when the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope(dim: int, theta: float, yarn):
    """YaRN's inverse frequencies [dim/2] (float32) and the factor on cos and
    sin, as DeepSeek-V2 computes them: the extrapolated frequencies
    ``theta^(-2i/dim)`` and the interpolated ones (divided by ``factor``)
    blend over a linear ramp between the dimensions that make ``beta_fast``
    and ``beta_slow`` rotations in ``original_max_position_embeddings``."""
    half = dim // 2

    def corr_dim(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = theta ** (-np.arange(half, dtype=np.float64) / half)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    freqs = extra / yarn.factor * ramp + extra * (1.0 - ramp)
    amp = (yarn_mscale(yarn.factor, yarn.mscale)
           / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return freqs.astype(np.float32), amp


def rope(x, positions, theta: float, yarn=None):
    """x: [..., S, H, hd]; positions: [..., S] int32.  ``yarn``: a
    :class:`repro.configs.base.Yarn` for YaRN-scaled frequencies."""
    hd = x.shape[-1]
    half = hd // 2
    amp = 1.0
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs, amp = yarn_rope(hd, theta, yarn)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap else x


# --------------------------------------------------------------------------- #
# Quantized / plain linear
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _ste_qmatmul(x2d, w, act_fmt, weight_fmt, impl, act_quant=True,
                 mode="rne", accum="bf16"):
    qw = quantize(w, weight_fmt, axis=-1)
    if act_quant:
        qx = quantize(x2d, act_fmt, mode=mode)
        return kops.matmul_q(
            qx, qw, impl=impl, mode=mode,
            compute_dtype=jnp.float32 if accum == "f32" else jnp.bfloat16,
        )
    # weight-only: dequantize w, keep activations in compute dtype
    from .quantize import resolve_weight

    wq = resolve_weight(qw, dtype=x2d.dtype)
    return (x2d @ wq).astype(jnp.float32)


def _ste_fwd(x2d, w, act_fmt, weight_fmt, impl, act_quant=True, mode="rne",
             accum="bf16"):
    return (
        _ste_qmatmul(x2d, w, act_fmt, weight_fmt, impl, act_quant, mode,
                     accum),
        (x2d, w),
    )


def _ste_bwd(act_fmt, weight_fmt, impl, act_quant, mode, accum, res, g):
    x2d, w = res
    g = g.astype(w.dtype)
    return (g @ w.T).astype(x2d.dtype), (x2d.T @ g).astype(w.dtype)


_ste_qmatmul.defvjp(_ste_fwd, _ste_bwd)


def _qlinear_legacy(x, w, qcfg, b=None):
    """The historical QuantConfig string-kwarg body, preserved verbatim.

    Reached only when ``REPRO_FORCE_LEGACY_QUANTCONFIG=1`` routes
    ``cfg.policy`` back to a QuantConfig (the deprecation-shim CI job);
    pinned bit-identical to the policy path by ``tests/test_numerics.py``.
    """
    from ..numerics import is_quantized_weight

    if is_quantized_weight(w):
        if qcfg is not None and qcfg.enabled and qcfg.act_quant:
            from .quantize import static_qmatmul

            shape = x.shape
            n_out = (w.shape if hasattr(w, "shape") else w["codes"].shape)[-1]
            y = static_qmatmul(x.reshape(-1, shape[-1]), w, qcfg)
            y = y.reshape(*shape[:-1], n_out).astype(x.dtype)
            if b is not None:
                y = y + b
            return y
        from .quantize import resolve_weight

        w = resolve_weight(w, qcfg.weight_fmt if qcfg else "e4m3", x.dtype)
    if qcfg is None or not qcfg.enabled:
        y = x @ w
    else:
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        y = _ste_qmatmul(x2d, w, qcfg.act_fmt, qcfg.weight_fmt,
                         qcfg.matmul_impl, qcfg.act_quant)
        y = y.reshape(*shape[:-1], w.shape[-1]).astype(x.dtype)
    if b is not None:
        y = y + b
    return y


def qlinear(x, w, pol, b=None, site: str = ""):
    """[..., D_in] @ [D_in, D_out] under the numerics policy.

    ``pol`` is a :class:`repro.numerics.Policy` (or the legacy
    ``QuantConfig`` shim, or None).  ``w`` may be a static-quantized
    :class:`QTensor` (weight-only FP8): with activation quantization on,
    the stored codes feed the quantized matmul directly (impl/blocks
    picked by the autotuner); otherwise the weight is decoded by integer
    bit placement right before the matmul.  Either way only 1 byte/param
    crosses HBM.  ``site`` names the call site for per-site policy
    overrides (``"blocks.0.attn.wq"`` style).
    """
    from .. import numerics

    if pol is not None and numerics.is_legacy_config(pol):
        return _qlinear_legacy(x, w, pol, b)
    return numerics.matmul(x, w, pol, site=site, bias=b)


# --------------------------------------------------------------------------- #
# Gated MLP
# --------------------------------------------------------------------------- #
def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x, approximate=True)


def gated_mlp(x, p, pol, act_fn="silu", site: str = "ffn"):
    """SwiGLU/GeGLU: down( act(gate(x)) * up(x) ).

    When the policy quantizes elementwise ops, the gate*up product runs
    through the paper's FP8 LNS multiply (kernels.fp8_elementwise)
    instead of an f32 multiply.
    """
    from .. import numerics
    from ..parallel.hints import hint

    g = _act(qlinear(x, p["w_gate"], pol, site=f"{site}.w_gate"), act_fn)
    u = qlinear(x, p["w_up"], pol, site=f"{site}.w_up")
    # Serving TP: w_gate/w_up columns shard over the model axis, so g/u
    # (and the elementwise gate*up) compute on ff shards; roles resolve
    # only inside the serving engine's hint context (no-ops elsewhere).
    g = hint(g, "ffn_hidden")
    u = hint(u, "ffn_hidden")
    if pol is not None and numerics.is_legacy_config(pol):
        # preserved QuantConfig string path (REPRO_FORCE_LEGACY_QUANTCONFIG)
        if pol.enabled and pol.elementwise:
            qg = quantize(g, pol.act_fmt)
            qu = quantize(u, pol.act_fmt)
            h = kops.elementwise_q("mul", qg, qu, mode=pol.mode)
            h = h.dequantize().astype(x.dtype)
        else:
            h = g * u
    else:
        h = numerics.elementwise("mul", g, u, pol, site=f"{site}.gate_up")
    # All-gather the ff-sharded hidden BEFORE w_down: the contraction is
    # computed whole on every shard — no partial sums, bit-identical TP.
    h = hint(h, "ffn_gather")
    return qlinear(h, p["w_down"], pol, site=f"{site}.w_down")


# --------------------------------------------------------------------------- #
# Attention (chunked, online softmax: flash-style in pure JAX)
# --------------------------------------------------------------------------- #
NEG_INF = -2.0e30


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int):
    """[Sq, Sk] additive bias from position indices."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(ok, 0.0, NEG_INF)


def chunked_attention(
    q, k, v, *,
    causal=True, window=0, cap=0.0, q_offset=0,
    q_chunk=512, kv_chunk=1024, k_len: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
):
    """GQA attention, O(q_chunk*kv_chunk) memory.

    q: [B, Sq, H, hd]; k/v: [B, Sk, KV, hd] with H % KV == 0.
    ``q_offset``: position of q[0] in the kv sequence (prefill continuation).
    ``k_len``: optional dynamic valid kv length (decode against a cache).
    ``scale``: the softmax scale (default ``hd ** -0.5``).
    """
    B, Sq0, H, hd = q.shape
    _, Sk0, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    # Pad the sequence up to a chunk multiple instead of shrinking chunks to
    # a divisor: ragged lengths (llava's 4096+576, whisper's 1500) previously
    # forced 64-wide chunks (33344 = 2^6 x 521), inflating op count and
    # intermediate HBM traffic ~8x.  Padded k rows are masked via k_len;
    # padded q rows are computed and sliced off.
    sp = hint_meta("sp")
    use_sp = bool(sp) and Sq0 % sp == 0 and Sk0 % sp == 0 and Sq0 // sp >= 16
    if use_sp:
        q_chunk = min(q_chunk, Sq0 // sp)
        kv_chunk = min(kv_chunk, Sk0 // sp)
    q_chunk = min(q_chunk, Sq0)
    kv_chunk = min(kv_chunk, Sk0)
    pad_q = (-Sq0) % q_chunk
    pad_k = (-Sk0) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_len = jnp.asarray(Sk0) if k_len is None else jnp.minimum(k_len, Sk0)
    Sq, Sk = Sq0 + pad_q, Sk0 + pad_k
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    if use_sp:
        out = _attention_sp(
            q, k, v, causal=causal, window=window, cap=cap, q_offset=q_offset,
            q_chunk=q_chunk, kv_chunk=kv_chunk, k_len=k_len, scale=scale,
        )
        return out[:, :Sq0]

    qc = q.reshape(B, nq, q_chunk, KV, G, hd)
    kc = k.reshape(B, nk, kv_chunk, KV, hd)
    vc = v.reshape(B, nk, kv_chunk, KV, dv)
    scale = hd ** -0.5 if scale is None else scale

    def q_step(qi_and_chunk):
        qi, qb = qi_and_chunk  # qb: [B, q_chunk, KV, G, hd]
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, kb, vb = kj_and_kv
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqkgd,btkd->bkgqt", qb.astype(jnp.float32), kb.astype(jnp.float32)
            ) * scale
            s = softcap(s, cap)
            bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
            if k_len is not None:
                bias = bias + jnp.where(k_pos[None, :] < k_len, 0.0, NEG_INF)
            s = s + bias[None, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vb.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, dv), jnp.float32)
        ks = (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), ks)
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        return out  # [B, KV, G, q_chunk, hd]

    outs = jax.lax.map(q_step, (jnp.arange(nq), jnp.moveaxis(qc, 1, 0)))
    # outs: [nq, B, KV, G, q_chunk, hd] -> [B, Sq, H, hd]
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)
    out = out.reshape(B, KV * G, Sq, dv).transpose(0, 2, 1, 3).astype(q.dtype)
    return out[:, :Sq0]


def _attention_sp(
    q, k, v, *, causal, window, cap, q_offset, q_chunk, kv_chunk, k_len,
    scale=None,
):
    """Sequence-parallel attention: q-chunk dim vectorized (sharded over
    ``model``), online-softmax scan over kv chunks.  Per-device score
    memory is B * (Sq/sp) * kv_chunk * H_local."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    qc = q.reshape(B, nq, q_chunk, KV, G, hd)  # nq sharded over model
    kc = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, dv), 1, 0)
    scale = hd**-0.5 if scale is None else scale
    q_pos = q_offset + (
        jnp.arange(nq)[:, None] * q_chunk + jnp.arange(q_chunk)[None, :]
    )  # [nq, q_chunk]

    def kv_step(carry, kj_and_kv):
        m, l, acc = carry  # [B, nq, KV, G, q_chunk(, dv)]
        kj, kb, vb = kj_and_kv
        k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum(
            "bnqkgd,btkd->bnkgqt", qc.astype(jnp.float32), kb.astype(jnp.float32)
        ) * scale
        s = softcap(s, cap)
        ok = jnp.ones((nq, q_chunk, kv_chunk), bool)
        if causal:
            ok &= q_pos[:, :, None] >= k_pos[None, None, :]
        if window:
            ok &= q_pos[:, :, None] - k_pos[None, None, :] < window
        bias = jnp.where(ok, 0.0, NEG_INF)
        if k_len is not None:
            bias = bias + jnp.where(k_pos < k_len, 0.0, NEG_INF)[None, None, :]
        s = s + bias[None, :, None, None]
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bnkgqt,btkd->bnkgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, nq, KV, G, q_chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, KV, G, q_chunk), jnp.float32)
    a0 = jnp.zeros((B, nq, KV, G, q_chunk, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
    out = acc / jnp.maximum(l, 1e-37)[..., None]  # [B, nq, KV, G, q_chunk, dv]
    out = out.transpose(0, 1, 4, 2, 3, 5).reshape(B, Sq, KV * G, dv)
    return out.astype(q.dtype)


def decode_attention(q, k, v, *, pos, window=0, cap=0.0, ring=False):
    """Single-position attention against a full-length cache.

    q: [B, 1, H, hd]; k/v: [B, S, KV, hd]; ``pos``: current position (the
    number of valid cache entries) — a scalar, or a [B] vector when slots
    in a serving batch sit at different positions.  Two-pass stable softmax
    keeps the reduction explicit so a sequence-sharded cache
    (SP/flash-decoding) turns the max/sum into cheap collectives under pjit.
    """
    B, _, H, hd = q.shape
    _, S, KV, _ = k.shape
    dv = v.shape[-1]
    G = H // KV
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) * hd**-0.5
    s = softcap(s, cap)
    t = jnp.arange(S)
    if ring:
        # ring cache of length S: all slots valid once pos >= S - 1
        ok = (t[None, :] <= pos[:, None]) | (pos[:, None] >= S)
    else:
        ok = t[None, :] <= pos[:, None]
        if window:
            ok &= (pos[:, None] - t[None, :]) < window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    num = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    den = p.sum(-1, keepdims=True)
    out = (num / jnp.maximum(den, 1e-37)).reshape(B, 1, H, dv)
    return out.astype(q.dtype)
