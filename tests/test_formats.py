"""Codec, oracle and saturating-op tests, incl. hypothesis property tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import lns
from repro.core.formats import E4M3, E5M2, FORMATS
from repro.core.rounding import Oracle


def test_format_constants():
    assert E5M2.bias == 15 and E5M2.B == 60
    assert E4M3.bias == 7 and E4M3.B == 56
    assert E5M2.max_normal == 57344.0
    assert E4M3.max_normal == 448.0
    assert E5M2.min_normal == 2.0**-14
    assert E4M3.min_normal == 2.0**-6
    assert E4M3.nan_code == 0x7F


@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_decode_monotone_on_normals(fmt):
    vals = fmt.normal_values()
    assert np.all(np.diff(vals) > 0)
    assert vals[0] == fmt.min_normal
    assert vals[-1] == fmt.max_normal


@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_decode_special_values(fmt):
    lut = fmt.decode(np.arange(256, dtype=np.uint8))
    assert lut[0] == 0.0
    assert np.isnan(lut[fmt.nan_code])
    if fmt.has_inf:
        assert np.isposinf(lut[fmt.inf_code])
        assert np.isneginf(lut[fmt.inf_code | 0x80])
    # sign symmetry
    mags = np.arange(1, 0x7F, dtype=np.uint8)
    finite = ~np.isnan(lut[mags]) & np.isfinite(lut[mags])
    np.testing.assert_array_equal(lut[mags][finite], -lut[mags | 0x80][finite])


@given(code=st.integers(0, 255))
@settings(max_examples=256, deadline=None)
def test_float32_lut_matches_decode(code):
    for fmt in (E5M2, E4M3):
        lut = fmt.code_to_float32_bits()
        ref = fmt.decode(np.uint8(code))
        if np.isnan(ref):
            assert np.isnan(lut[code])
        else:
            assert lut[code] == np.float32(ref)


# --------------------------------------------------------------------------- #
# Saturating production op
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_lns_op_matches_raw_in_domain(fmt):
    X, Y = np.meshgrid(np.arange(256, dtype=np.uint8),
                       np.arange(256, dtype=np.uint8), indexing="ij")
    X, Y = X.ravel(), Y.ravel()
    oracle = Oracle(fmt)
    _, valid = oracle.quantize_all("mul", X, Y)
    raw = np.asarray(lns.lns_op_raw(fmt, "mul", "rne", X, Y))
    safe = np.asarray(lns.lns_op(fmt, "mul", "rne", X, Y))
    np.testing.assert_array_equal(raw[valid], safe[valid])


@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_lns_op_specials(fmt):
    z = np.uint8(0)
    one = np.asarray(
        np.where(fmt.decode(np.arange(256, dtype=np.uint8)) == 1.0)[0][0],
        dtype=np.uint8,
    )
    big = np.uint8(fmt.max_normal_code)
    # 0 * 1 = 0
    assert lns.lns_op(fmt, "mul", "rne", z, one) == 0
    # max * max saturates to max (not wraparound garbage)
    out = int(lns.lns_op(fmt, "mul", "rne", big, big))
    assert out == fmt.max_normal_code
    # min * min flushes to zero
    small = np.uint8(fmt.min_normal_code)
    assert int(lns.lns_op(fmt, "mul", "rne", small, small)) == 0
    # sqrt of negative -> NaN
    neg = np.uint8(one | 0x80)
    assert int(lns.lns_op(fmt, "sqrt", "rne", neg)) == fmt.nan_code
    # NaN propagates
    nan = np.uint8(fmt.nan_code)
    assert int(lns.lns_op(fmt, "mul", "rne", nan, one)) == fmt.nan_code
    # recip(0) saturates
    assert int(lns.lns_op(fmt, "recip", "rne", z)) & 0x7F == fmt.max_normal_code


@given(
    xe=st.integers(-3, 3), xm=st.integers(0, 3),
    ye=st.integers(-3, 3), ym=st.integers(0, 3),
    sx=st.booleans(), sy=st.booleans(),
)
@settings(max_examples=200, deadline=None)
def test_lns_mul_faithful_property_e5m2(xe, xm, ye, ym, sx, sy):
    """Property: saturating LNS mul is faithful wherever result is normal."""
    fmt = E5M2
    xc = ((xe + fmt.bias) << 2 | xm) | (0x80 if sx else 0)
    yc = ((ye + fmt.bias) << 2 | ym) | (0x80 if sy else 0)
    x, y = fmt.decode(np.uint8(xc)), fmt.decode(np.uint8(yc))
    r = x * y
    if not (fmt.min_normal <= abs(r) <= fmt.max_normal):
        return
    got = fmt.decode(np.asarray(lns.lns_op(fmt, "mul", "faithful", np.uint8(xc), np.uint8(yc))))
    vals = fmt.normal_values()
    lo = vals[np.searchsorted(vals, abs(r), side="right") - 1]
    hi_i = np.searchsorted(vals, abs(r), side="left")
    hi = vals[min(hi_i, len(vals) - 1)]
    assert min(lo, hi) <= abs(got) <= max(lo, hi)
    assert np.sign(got) == np.sign(r)
