"""Pallas TPU flash attention: tiled online-softmax, GQA-aware BlockSpecs.

Addresses the dominant roofline term of the dense train/prefill cells
(EXPERIMENTS.md §Roofline): the pure-JAX chunked attention materializes
score tiles through HBM at XLA fusion granularity, while this kernel keeps
the whole online-softmax state (m, l, acc) in VMEM scratch across the KV
grid axis — scores never leave the core.

Grid: (B*H, Sq/bq, Sk/bk), KV innermost (arbitrary).  GQA is handled in
the BlockSpec index maps (query head h reads kv head h // (H/KV)) — the
KV tensor is never repeated in memory.  Causal/sliding-window/softcap are
mask arithmetic on absolute positions.

VMEM at (bq, bk) = (128, 128), hd = 128: q 32 KiB + k/v 64 KiB + acc
64 KiB + scores ~128 KiB f32 << 16 MiB.  MXU-aligned tile shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import NEG_INF


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq, bk, nk, scale, causal, window, cap, k_len):
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [bq, hd]
    k = k_ref[0].astype(jnp.float32)  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)  # [bk, dv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]
    if cap:
        s = jnp.tanh(s / cap) * cap

    q_pos = i_q * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = i_k * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < k_len
    if causal:
        ok &= q_pos >= k_pos
    if window:
        ok &= q_pos - k_pos < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]  # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(i_k == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def flash_attention(
    q, k, v, *,
    causal: bool = True, window: int = 0, cap: float = 0.0,
    bq: Optional[int] = None, bk: Optional[int] = None, interpret: bool = False,
):
    """q: [B, Sq, H, hd]; k/v: [B, Sk, KV, hd|dv]; returns [B, Sq, H, dv].

    ``bq``/``bk`` default to the autotuner's tiling for (Sq, Sk, hd, dv);
    pass explicit values to pin them.
    """
    if bq is None or bk is None:
        from . import autotune

        abq, abk = autotune.flash_blocks(
            q.shape[1], k.shape[1], q.shape[-1], v.shape[-1], interpret=interpret
        )
        bq = abq if bq is None else bq
        bk = abk if bk is None else bk
    return _flash_attention(
        q, k, v, causal=causal, window=window, cap=cap,
        bq=bq, bk=bk, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "cap", "bq", "bk", "interpret"),
)
def _flash_attention(
    q, k, v, *,
    causal: bool, window: int, cap: float,
    bq: int, bk: int, interpret: bool,
):
    B, Sq0, H, hd = q.shape
    _, Sk0, KV, dv = v.shape
    G = H // KV
    scale = hd**-0.5

    bq = min(bq, Sq0 if Sq0 % 8 == 0 else bq)
    bk = min(bk, Sk0 if Sk0 % 8 == 0 else bk)
    pad_q = (-Sq0) % bq
    pad_k = (-Sk0) % bk
    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    Sq, Sk = Sq0 + pad_q, Sk0 + pad_k

    # fold: q [B*H, Sq, hd]; k/v stay [B*KV, Sk, *] (GQA via index map)
    qf = qf.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kf.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dv)
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
        causal=causal, window=window, cap=cap, k_len=Sk0,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda b, i, j, KV=KV, G=G, H=H: (b // H * KV + (b % H) // G, j, 0)),
            pl.BlockSpec((1, bk, dv),
                         lambda b, i, j, KV=KV, G=G, H=H: (b // H * KV + (b % H) // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, Sq, dv).transpose(0, 2, 1, 3)
    return out[:, :Sq0]
