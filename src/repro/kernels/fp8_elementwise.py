"""Pallas TPU kernel: the paper's six FP8 ops, elementwise over code tensors.

This is the direct TPU analogue of the paper's SIMD-integer motivation: an
FP8 multiply/divide/sqrt/rsqrt on the VPU costs a handful of int8-width adds
and bit ops instead of a decode -> f32 transcendental -> encode round trip.
Used by the quantized model fabric for SwiGLU gating products, RMSNorm
rsqrt, and KV-scale division.

Inputs are flattened and tiled as (rows, 128) lanes — uint8 codes in,
uint8 codes out, saturating semantics (core.lns.lns_op).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import FORMATS
from ..core.lns import lns_op

LANES = 128
DEFAULT_BLOCK_ROWS = 256


def _unary_kernel(x_ref, o_ref, *, fmt, op, mode):
    o_ref[...] = lns_op(fmt, op, mode, x_ref[...])


def _binary_kernel(x_ref, y_ref, o_ref, *, fmt, op, mode):
    o_ref[...] = lns_op(fmt, op, mode, x_ref[...], y_ref[...])


def fp8_elementwise(
    op: str,
    x_codes,
    y_codes=None,
    *,
    fmt: str = "e4m3",
    mode: str = "rne",
    block_rows: Optional[int] = None,
    interpret: bool = False,
):
    """Apply a paper op to uint8 code tensors of any (broadcast-equal) shape.

    ``block_rows=None`` asks the autotuner (``kernels.autotune``) for the
    row-tile size; pass an explicit value to pin it.
    """
    if block_rows is None:
        from . import autotune

        block_rows = autotune.elementwise_block_rows(
            x_codes.size, fmt=fmt, op=op, mode=mode, interpret=interpret
        )
    return _fp8_elementwise(
        op, x_codes, y_codes, fmt=fmt, mode=mode,
        block_rows=block_rows, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("op", "fmt", "mode", "block_rows", "interpret")
)
def _fp8_elementwise(
    op: str,
    x_codes,
    y_codes=None,
    *,
    fmt: str,
    mode: str,
    block_rows: int,
    interpret: bool,
):
    assert x_codes.dtype == jnp.uint8
    shape = x_codes.shape
    n = x_codes.size
    rows = -(-n // LANES)  # ceil
    pad = rows * LANES - n
    xf = jnp.pad(x_codes.reshape(-1), (0, pad)).reshape(rows, LANES)
    rows_p = -(-rows // block_rows) * block_rows
    if rows_p != rows:
        xf = jnp.pad(xf, ((0, rows_p - rows), (0, 0)))
    grid = (rows_p // block_rows,)
    fmt_obj = FORMATS[fmt]

    if y_codes is None:
        kernel = functools.partial(_unary_kernel, fmt=fmt_obj, op=op, mode=mode)
        args = (xf,)
        in_specs = [pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))]
    else:
        assert y_codes.shape == shape and y_codes.dtype == jnp.uint8
        yf = jnp.pad(y_codes.reshape(-1), (0, pad)).reshape(rows, LANES)
        if rows_p != rows:
            yf = jnp.pad(yf, ((0, rows_p - rows), (0, 0)))
        kernel = functools.partial(_binary_kernel, fmt=fmt_obj, op=op, mode=mode)
        args = (xf, yf)
        in_specs = [
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.uint8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)[:n].reshape(shape)
