"""Quantization codec tests: encode correctness vs oracle, QTensor properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.formats import E4M3, E5M2
from repro.core.quant import QTensor, decode, encode, quantize
from repro.core.rounding import Oracle


@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_encode_roundtrips_all_codes(fmt):
    """Every finite FP8 value must encode back to its own code."""
    codes = np.arange(256, dtype=np.uint8)
    vals = fmt.decode(codes)
    finite = np.isfinite(vals)
    # exclude subnormals (FTZ semantics) and -0 (encodes to +0 magnitude)
    normal_or_zero = fmt.is_normal(codes.astype(np.int64)) | ((codes & 0x7F) == 0)
    mask = finite & normal_or_zero
    got = np.asarray(encode(jnp.asarray(vals[mask], jnp.float32), fmt))
    want = codes[mask]
    # -0.0 -> 0x80 keeps sign; values equal so compare decoded
    np.testing.assert_array_equal(fmt.decode(got), fmt.decode(want))


@pytest.mark.parametrize("fmt", [E5M2, E4M3], ids=lambda f: f.name)
def test_encode_rne_matches_oracle_on_midpoint_grid(fmt):
    """Check RNE on a dense grid incl. exact midpoints between normals."""
    vals = fmt.normal_values()
    mids = 0.5 * (vals[:-1] + vals[1:])
    quarter = vals[:-1] + 0.25 * (vals[1:] - vals[:-1])
    grid = np.concatenate([vals, mids, quarter, -mids, -vals])
    got = np.asarray(encode(jnp.asarray(grid, jnp.float32), fmt))
    dec = fmt.decode(got)
    # RNE: |dec - grid| <= half spacing, ties to even code
    codes = fmt.all_normal_codes()
    for g, d, c in zip(grid, dec, got):
        ag = abs(g)
        i = np.searchsorted(vals, ag)
        lo = vals[max(i - 1, 0)]
        hi = vals[min(i, len(vals) - 1)]
        best = min(abs(lo - ag), abs(hi - ag))
        assert abs(abs(d) - ag) == pytest.approx(best, abs=0.0), (g, d)
        if abs(lo - ag) == abs(hi - ag) and lo != hi:  # exact tie
            assert (int(c) & 1) == 0, f"tie not to even at {g} -> {d}"


def test_encode_specials():
    for fmt in (E5M2, E4M3):
        out = np.asarray(
            encode(jnp.asarray([np.nan, np.inf, -np.inf, 0.0, -0.0, 1e9, -1e9], jnp.float32), fmt)
        )
        assert out[0] == fmt.nan_code
        assert out[1] == fmt.max_normal_code  # saturating
        assert out[2] == (fmt.max_normal_code | 0x80)
        assert out[3] == 0
        assert fmt.decode(out[5]) == fmt.max_normal
        assert fmt.decode(out[6]) == -fmt.max_normal


def test_encode_ftz():
    fmt = E4M3
    tiny = fmt.min_normal
    xs = jnp.asarray([tiny, 0.74 * tiny, 0.5 * tiny, 0.26 * tiny, 0.0], jnp.float32)
    out = np.asarray(encode(xs, fmt))
    assert out[0] == fmt.min_normal_code
    assert out[1] == fmt.min_normal_code  # rounds up to min normal
    assert out[2] == 0  # tie -> zero (even)
    assert out[3] == 0


@given(data=st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_quantize_dequantize_error_bound(data):
    x = jnp.asarray(np.array(data, dtype=np.float32))
    for fmt in (E5M2, E4M3):
        q = quantize(x, fmt.name)
        y = np.asarray(q.dequantize())
        amax = max(abs(np.asarray(x)).max(), 1e-12)
        # relative-to-amax error bounded by half ulp at the top binade + FTZ
        tol = amax * 2.0 ** (-fmt.man_bits) / 2 * 1.0001 + float(q.scale) * fmt.min_normal
        assert np.all(np.abs(y - np.asarray(x)) <= tol + 1e-12)


def test_quantize_per_channel_axis():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8).astype(np.float32) * np.array([[1.0], [10.0], [100.0], [1000.0]]))
    q = quantize(x, "e4m3", axis=0)
    assert q.scale.shape == (4, 1)
    y = np.asarray(q.dequantize())
    rel = np.abs(y - np.asarray(x)).max(axis=1) / np.abs(np.asarray(x)).max(axis=1)
    assert np.all(rel < 2.0 ** (-3) / 2 * 1.01)


def test_qtensor_is_pytree():
    q = quantize(jnp.ones((2, 2)), "e5m2")
    leaves = jax.tree_util.tree_leaves(q)
    assert len(leaves) == 2
    q2 = jax.jit(lambda t: t)(q)
    np.testing.assert_array_equal(np.asarray(q.codes), np.asarray(q2.codes))


def test_stochastic_rounding_unbiased():
    fmt = E4M3
    x = jnp.full((20000,), 1.0 + 1.0 / 16.0, jnp.float32)  # between 1.0 and 1.125
    out = decode(encode(x, fmt, "stochastic", key=jax.random.PRNGKey(0)), fmt)
    m = float(jnp.mean(out))
    assert 1.05 < m < 1.075  # expectation = 1.0625
