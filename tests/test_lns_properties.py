"""Hypothesis property tests on the LNS arithmetic's algebraic invariants."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import lns
from repro.core.formats import E4M3, E5M2

FMTS = {"e5m2": E5M2, "e4m3": E4M3}


def norm_codes(fmt):
    return st.integers(fmt.min_normal_code, fmt.max_normal_code)


def signed(fmt):
    return st.tuples(norm_codes(fmt), st.booleans()).map(
        lambda t: np.uint8(t[0] | (0x80 if t[1] else 0))
    )


@given(fname=st.sampled_from(["e5m2", "e4m3"]), data=st.data())
@settings(max_examples=300, deadline=None)
def test_mul_commutative(fname, data):
    fmt = FMTS[fname]
    x = data.draw(signed(fmt))
    y = data.draw(signed(fmt))
    for mode in ("rne", "faithful"):
        a = lns.lns_op(fmt, "mul", mode, x, y)
        b = lns.lns_op(fmt, "mul", mode, y, x)
        assert int(a) == int(b), (hex(int(x)), hex(int(y)), mode)


@given(fname=st.sampled_from(["e5m2", "e4m3"]), data=st.data())
@settings(max_examples=300, deadline=None)
def test_square_equals_self_mul_within_one_ulp(fname, data):
    """square(x) and mul(x, x) quantize the same exact value: both must be
    within one code step of each other for round-to-nearest."""
    fmt = FMTS[fname]
    x = data.draw(signed(fmt))
    sq = int(lns.lns_op(fmt, "square", "rne", x)) & 0x7F
    mm = int(lns.lns_op(fmt, "mul", "rne", x, x)) & 0x7F
    assert abs(sq - mm) <= 1


@given(fname=st.sampled_from(["e5m2", "e4m3"]), data=st.data())
@settings(max_examples=300, deadline=None)
def test_mul_div_roundtrip_faithful(fname, data):
    """(x * y) / y stays within ~1 ulp of x (two faithful roundings)."""
    fmt = FMTS[fname]
    x = data.draw(norm_codes(fmt).map(np.uint8))
    y = data.draw(norm_codes(fmt).map(np.uint8))
    xv, yv = float(fmt.decode(np.asarray(x))), float(fmt.decode(np.asarray(y)))
    if not (fmt.min_normal <= abs(xv * yv) <= fmt.max_normal):
        return  # saturated/flushed product: roundtrip not defined
    p = lns.lns_op(fmt, "mul", "rne", x, y)
    back = lns.lns_op(fmt, "div", "rne", p, y)
    # within ONE code step, exhaustively verified for both formats
    assert abs((int(back) & 0x7F) - (int(x) & 0x7F)) <= 1


@given(fname=st.sampled_from(["e5m2", "e4m3"]), data=st.data())
@settings(max_examples=300, deadline=None)
def test_sqrt_rsqrt_product_is_recip(fname, data):
    """sqrt(x) in LNS is X/2-ish; rsqrt(x)*sqrt(x)*... sanity: the decoded
    values satisfy sqrt(x)^2 ~ x and rsqrt(x) ~ 1/sqrt(x) within 2 ulp."""
    fmt = FMTS[fname]
    x = data.draw(norm_codes(fmt).map(np.uint8))
    s = lns.lns_op(fmt, "sqrt", "rne", x)
    r = lns.lns_op(fmt, "rsqrt", "rne", x)
    sv = float(fmt.decode(np.asarray(s)))
    rv = float(fmt.decode(np.asarray(r)))
    xv = float(fmt.decode(np.asarray(x)))
    assert sv > 0 and rv > 0
    ulp = 2.0 ** (-fmt.man_bits)
    assert abs(sv * sv - xv) / xv < 4 * ulp
    assert abs(sv * rv - 1.0) < 4 * ulp


@given(fname=st.sampled_from(["e5m2", "e4m3"]), data=st.data())
@settings(max_examples=300, deadline=None)
def test_directed_modes_bracket_nearest(fname, data):
    """Wherever RU and RD both exist, RD <= RN_e <= RU on decoded values."""
    fmt = FMTS[fname]
    from repro.core.carry_ins import CARRY_INS

    x = data.draw(norm_codes(fmt).map(np.uint8))
    y = data.draw(norm_codes(fmt).map(np.uint8))
    op = data.draw(st.sampled_from(["mul", "div", "square", "recip", "sqrt", "rsqrt"]))
    specs = CARRY_INS[(fmt.name, op)]
    if specs["ru"] is None or specs["rd"] is None:
        return
    args = (x, y) if op in ("mul", "div") else (x,)
    vals = {}
    for mode in ("rd", "rne", "ru"):
        c = lns.lns_op(fmt, op, mode, *args)
        if not bool(np.asarray(fmt.is_normal(np.int64(int(c))))):
            return  # out-of-range: saturation breaks the ordering contract
        vals[mode] = float(fmt.decode(np.asarray(c)))
    assert vals["rd"] <= vals["rne"] <= vals["ru"]
