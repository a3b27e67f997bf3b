"""Pallas TPU kernels for FP8 matmul over uint8 LNS codes.

Three implementations, all tiled for VMEM with explicit BlockSpecs:

* ``lns`` (paper-faithful, vectorized): each scalar product is the paper's
  integer addition ``X + Y + K + c_in`` on the raw codes (eqs. 6/29 +
  Tables 2/3 carry-ins).  All per-operand work — sign/mantissa bit fields,
  the per-operand halves of the factored carry-in expressions, the decode
  constants — is done once per element (``common.lns_prepare``), not per
  product; K is processed in sub-chunks of ``ck`` codes as [ck, bm, bn]
  broadcast integer tiles reduced over ck in one step: bk/ck wide VPU ops
  instead of bk sequential rank-1 updates.  The x tile is staged K-major
  in VMEM scratch so each sub-chunk is a sublane slice of a ref.  Product
  codes are decoded to f32 by exponent/mantissa bit placement (no LUT) and
  accumulated in f32.
  No floating-point multiplier is ever used — the multiply cost is integer
  adds, exactly the paper's proposition.

* ``lns_loop`` (the seed kernel, kept as the perf baseline): identical
  numerics, but the K dimension is a ``fori_loop`` of rank-1 slices —
  O(bk) sequential VPU steps per [bm, bn] tile.  Exists so the perf
  trajectory harness (benchmarks/run.py --json) can keep proving the
  vectorized kernel's speedup against it.

* ``fused_dequant`` (beyond-paper TPU adaptation): decode both code tiles
  to ``compute_dtype`` once and feed the MXU.  Same numerics as
  decode-then-matmul, but fused so codes (1 byte/elem) are what crosses
  HBM->VMEM: 2x less weight traffic than bf16.  Operands may use different
  formats (e.g. E5M2 activations x E4M3 weights).

Block sizes come from ``kernels.autotune`` unless given explicitly; the
``lns`` tiling is (bm, bn, bk, ck).  VMEM at the default (128, 128, 128, 16)
blocks: x/w tiles 32 KiB + out 64 KiB + [ck, bm, bn] int32/f32 temporaries
~ a few MiB << 16 MiB/core.  Matmul dims are multiples of 128 => MXU/VPU
lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import FORMATS
from .common import code_to_f32, lns_combine, lns_mul_to_f32, lns_prepare

DEFAULT_BLOCKS = (128, 128, 128)
DEFAULT_CK = 16


def _stage_x_transposed(x_ref, xt_ref):
    """Widen the x code tile once and store it K-major in VMEM scratch, so
    every K slice below is a sublane slice of a ref (Mosaic slices refs
    along sublanes at any offset, along lanes only at multiples of 128)."""
    xt_ref[...] = x_ref[...].astype(jnp.int32).T


def _lns_kernel(x_ref, w_ref, o_ref, xt_ref, *, fmt, mode, bk, ck):
    """Grid (M/bm, N/bn, K/bk), K innermost; o block revisited across k.

    The inner product is bk/ck vectorized [ck, bm, bn] combine steps, each
    reduced over its leading (major) axis; every per-operand bit field is
    prepared once per element.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _stage_x_transposed(x_ref, xt_ref)

    def chunk(c, acc):
        k0 = pl.multiple_of(c * ck, ck)
        # broadcast the codes, not the prepared fields: Mosaic cannot
        # reshape boolean masks, and the integer prepare commutes with it
        xs = xt_ref[pl.ds(k0, ck), :][:, :, None]                  # [ck, bm, 1]
        ws = w_ref[pl.ds(k0, ck), :].astype(jnp.int32)[:, None, :]  # [ck, 1, bn]
        prod = lns_combine(lns_prepare(xs, fmt, mode, side="x"),
                           lns_prepare(ws, fmt, mode, side="y"), fmt)
        return acc + prod.sum(axis=0)  # [ck, bm, bn] -> [bm, bn] f32

    acc = jax.lax.fori_loop(0, bk // ck, chunk,
                            jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc


def _lns_loop_kernel(x_ref, w_ref, o_ref, xt_ref, wi_ref, *, fmt, mode, bk):
    """The seed kernel: sequential rank-1 k-loop.  Perf baseline only."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _stage_x_transposed(x_ref, xt_ref)
    wi_ref[...] = w_ref[...].astype(jnp.int32)  # 1-row loads need 32-bit rows

    def body(k, acc):
        xk = xt_ref[pl.ds(k, 1), :].T                       # [bm, 1]
        wk = wi_ref[pl.ds(k, 1), :]                         # [1, bn]
        return acc + lns_mul_to_f32(xk, wk, fmt, mode)      # [bm, bn] f32

    acc = jax.lax.fori_loop(0, bk, body, jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc


def _dequant_kernel(x_ref, w_ref, o_ref, *, fmt, w_fmt, compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = code_to_f32(x_ref[...], fmt).astype(compute_dtype)
    w = code_to_f32(w_ref[...], w_fmt).astype(compute_dtype)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def _pad_to(a, m0, m1):
    p0 = (-a.shape[0]) % m0
    p1 = (-a.shape[1]) % m1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))  # code 0 == value 0.0
    return a


def normalize_blocks(blocks, M: int, N: int, K: int):
    """Clamp a (bm, bn, bk[, ck]) request to the problem and tile grids.

    ``ck`` is clamped to the largest divisor of the (clamped) bk not above
    the request, so the chunked kernel always covers bk exactly.
    """
    if len(blocks) == 3:
        blocks = (*blocks, DEFAULT_CK)
    bm, bn, bk, ck = blocks
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    ck = max(1, min(ck, bk))
    while bk % ck:
        ck -= 1
    return bm, bn, bk, ck


def lns_matmul(
    x_codes,
    w_codes,
    *,
    fmt: str = "e4m3",
    mode: str = "rne",
    impl: str = "lns",
    blocks=None,
    interpret: bool = False,
    compute_dtype=jnp.float32,
    w_fmt: str | None = None,
):
    """f32[M, N] matmul of uint8 FP8 code matrices (scales applied by caller).

    ``blocks`` is (bm, bn, bk) or (bm, bn, bk, ck); None asks the autotuner
    (``kernels.autotune``), which serves measured tilings from its on-disk
    cache or sensible defaults.  ``w_fmt`` (fused_dequant only) lets the two
    operands use different FP8 formats.
    """
    M, K = x_codes.shape
    K2, N = w_codes.shape
    assert K == K2, (x_codes.shape, w_codes.shape)
    if w_fmt is None:
        w_fmt = fmt
    if impl in ("lns", "lns_loop") and w_fmt != fmt:
        raise ValueError("the paper's LNS product is single-format; use fused_dequant")
    if blocks is None:
        from . import autotune

        blocks = autotune.matmul_blocks(M, N, K, fmt=fmt, impl=impl,
                                        mode=mode, interpret=interpret)
    bm, bn, bk, ck = normalize_blocks(blocks, M, N, K)
    return _lns_matmul(
        x_codes, w_codes, fmt=fmt, mode=mode, impl=impl,
        blocks=(bm, bn, bk, ck), interpret=interpret,
        compute_dtype=compute_dtype, w_fmt=w_fmt,
    )


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "mode", "impl", "blocks", "interpret",
                     "compute_dtype", "w_fmt"),
)
def _lns_matmul(
    x_codes, w_codes, *, fmt, mode, impl, blocks, interpret, compute_dtype, w_fmt
):
    assert x_codes.dtype == jnp.uint8 and w_codes.dtype == jnp.uint8
    M, K = x_codes.shape
    _, N = w_codes.shape
    bm, bn, bk, ck = blocks

    xp = _pad_to(x_codes, bm, bk)
    wp = _pad_to(w_codes, bk, bn)
    Mp, Kp = xp.shape
    _, Np = wp.shape
    grid = (Mp // bm, Np // bn, Kp // bk)

    if impl == "lns":
        kernel = functools.partial(
            _lns_kernel, fmt=FORMATS[fmt], mode=mode, bk=bk, ck=ck
        )
    elif impl == "lns_loop":
        kernel = functools.partial(
            _lns_loop_kernel, fmt=FORMATS[fmt], mode=mode, bk=bk
        )
    elif impl == "fused_dequant":
        kernel = functools.partial(
            _dequant_kernel, fmt=FORMATS[fmt], w_fmt=FORMATS[w_fmt],
            compute_dtype=compute_dtype,
        )
    else:
        raise ValueError(f"unknown impl {impl!r}")

    scratch = {
        "lns": [pltpu.VMEM((bk, bm), jnp.int32)],
        "lns_loop": [pltpu.VMEM((bk, bm), jnp.int32),
                     pltpu.VMEM((bk, bn), jnp.int32)],
    }.get(impl, [])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]
