"""Shared helpers usable both inside Pallas kernel bodies and in jnp oracles."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.formats import FORMATS, FP8Format


def code_to_f32(codes, fmt: FP8Format | str):
    """uint8 FP8 codes -> float32, by bit placement (no LUT gather).

    Builds the f32 pattern with integer shifts: TPU-VPU friendly (gathers
    are slow on TPU; this is 5 int ops + a bitcast).  Normals and zero only:
    NaN codes map to 0 — the saturating LNS ops never emit NaN for finite
    inputs, and quantized-layer inputs are NaN-free by construction.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    c = codes.astype(jnp.uint32)
    sign = (c >> 7) & 0x1
    mag = c & 0x7F
    exp = (mag >> fmt.man_bits).astype(jnp.int32)
    man = (mag & fmt.man_mask).astype(jnp.uint32)
    f32_exp = (exp - fmt.bias + 127).astype(jnp.uint32)
    bits = (sign << 31) | (f32_exp << 23) | (man << (23 - fmt.man_bits))
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    is_normal = (mag >= fmt.min_normal_code) & (mag <= fmt.max_normal_code)
    return jnp.where(is_normal, val, 0.0)


# --------------------------------------------------------------------------- #
# The paper's integer-add multiply, split into per-operand preparation and a
# cheap per-product combine so a matmul kernel hoists all bit extraction out
# of the inner product (O(bm*bk + bk*bn) prepare work, O(bm*bk*bn) combines).
# --------------------------------------------------------------------------- #
class LNSOperand(NamedTuple):
    """Per-operand fields of the LNS product, extracted once per tile.

    All per-element arrays share the operand's shape; broadcasting two
    operands against each other is the caller's job (reshape before combine).
    """

    s31: jnp.ndarray              # uint32: sign bit already at bit 31
    mag: jnp.ndarray              # int32: magnitude code; x side carries the
    #                               folded LNS constant, f32 re-bias and any
    #                               constant carry-in, so combine is one add
    cmask: Optional[jnp.ndarray]  # int32 packed factored carry terms, or None
    #                               when the carry-in is a constant
    zero: jnp.ndarray             # bool: zero/subnormal operand (FTZ)
    bad: jnp.ndarray              # bool: NaN (or inf for e5m2) operand


def lns_prepare(codes, fmt: FP8Format | str, mode: str = "rne",
                side: str = "x") -> LNSOperand:
    """Extract everything per-operand about the paper's mul: bit fields,
    the factored carry-in halves (Tables 2/3), and special-value masks.

    ``side`` selects which half of the factored carry terms this operand
    feeds ("x" = left, "y" = right); the x side also absorbs every additive
    constant of the wide decode:

        K - 256                      the LNS mul constant (eq. 29),
        (127 - bias) << man_bits     f32 exponent re-bias, and
        the constant carry-in        for modes with c_in in {0, 1},

    so ``combine`` is ``mag_x + mag_y (+ c_in)`` followed by one shift.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    from ..core.carry_ins import mul_carry_constant, mul_carry_term_mask
    from ..core.lns import LNS_CONSTS

    Vi = jnp.asarray(codes).astype(jnp.int32)
    s31 = (Vi.astype(jnp.uint32) & 0x80) << 24
    mag = Vi & 0x7F
    if side == "x":
        K = LNS_CONSTS[(fmt.name, "mul")]
        folded = (K - 256) + ((127 - fmt.bias) << fmt.man_bits)
        const_cin = mul_carry_constant(fmt.name, mode)
        if const_cin is not None:
            folded += const_cin
        mag = mag + folded
    cmask = mul_carry_term_mask(fmt.name, mode, Vi, side)
    zero = (Vi & 0x7F) < fmt.min_normal_code
    if fmt.has_inf:
        bad = (Vi & 0x7F) >= (fmt.exp_mask << fmt.man_bits)
    else:
        bad = (Vi & 0x7F) == 0x7F
    return LNSOperand(s31=s31, mag=mag, cmask=cmask, zero=zero, bad=bad)


def lns_combine(px: LNSOperand, py: LNSOperand, fmt: FP8Format | str):
    """Finish the paper's integer-add product, decoded WIDE to float32.

    With the constants folded at prepare time the whole wide decode is:
    carry = one AND + compare (factored Tables 2/3 expressions), magnitude =
    one or two integer adds, and the f32 pattern is the pre-biased magnitude
    shifted into the exponent/mantissa fields — the mantissa low bits land in
    place because the re-bias constant is a multiple of 2^man_bits.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    mag = px.mag + py.mag
    if px.cmask is not None:
        mag = mag + ((px.cmask & py.cmask) != 0).astype(jnp.int32)
    bits = (px.s31 ^ py.s31) | (mag.astype(jnp.uint32) << (23 - fmt.man_bits))
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    val = jnp.where(px.zero | py.zero, 0.0, val)
    val = jnp.where(px.bad | py.bad, jnp.nan, val)
    return val


def lns_mul_to_f32(X, Y, fmt: FP8Format | str, mode: str = "rne"):
    """The paper's integer-add FP8 product, decoded WIDE to float32.

    The standalone multiplier of the paper emits an FP8 code, which would
    saturate products of near-max operands (|x*y| can reach max_normal^2).
    Inside a dot-product unit the natural design keeps the full integer LNS
    sum (a 9-bit quantity) and widens on decode — same integer-add multiply
    cost, no saturation, strictly more accurate accumulation.  The carry-in
    logic (Tables 2/3) is unchanged: it only depends on operand mantissas.

    Zero/subnormal operands contribute 0 (FTZ); NaN inputs propagate NaN.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    return lns_combine(
        lns_prepare(X, fmt, mode, side="x"),
        lns_prepare(Y, fmt, mode, side="y"),
        fmt,
    )


def f32_to_code(x, fmt: FP8Format | str, mode: str = "rne"):
    """float32 -> uint8 FP8 codes; thin alias of core.quant.encode (jit-safe
    and Pallas-safe: pure bit manipulation)."""
    from ..core.quant import encode

    return encode(x, fmt, mode)
