"""The unified numerics-policy API: policy tree, QTensor carrier,
legacy-path bit-identity.

Pins the PR-4 acceptance contract:
  * JSON round-trip for every registered preset (+ random policies under
    hypothesis);
  * QTensor pytree behavior under jit / scan / vmap;
  * the policy-resolved compute paths are bit-identical to the legacy
    QuantConfig string-kwarg paths per format x rounding mode on
    exhaustive operand grids, and on greedy serving outputs;
  * mixed-format LNS matmuls are rejected at Policy construction (naming
    the op site) instead of deep inside tracing, and the legacy config
    that used to crash there now coerces and runs;
  * no raw fmt=/mode= string kwargs under src/repro/models/ (the CI lint,
    enforced here too).
"""
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro import numerics
from repro.configs import get_config, legacy_quant_config
from repro.configs.base import QuantConfig
from repro.core.quant import QTensor, decode, quantize
from repro.numerics import (
    LEGACY_QUANT_PRESETS,
    OpPolicy,
    Override,
    Policy,
    available_policies,
    get_policy,
)

jax.config.update("jax_platform_name", "cpu")


def bit_equal(a, b) -> bool:
    """Exact f32 bit equality (NaN == NaN, -0 != +0)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return bool(np.array_equal(a.view(np.uint32), b.view(np.uint32)))


# --------------------------------------------------------------------------- #
# JSON round trip + registry
# --------------------------------------------------------------------------- #
def test_json_roundtrip_every_preset():
    for name in available_policies():
        p = get_policy(name)
        assert Policy.from_json(p.to_json()) == p, name
        assert Policy.from_dict(p.to_dict()) == p, name


def test_json_roundtrip_with_overrides():
    p = get_policy("train_fp8_attn_e4m3")
    assert p.overrides  # the preset actually exercises overrides
    q = Policy.from_json(p.to_json())
    assert q.overrides == p.overrides
    assert q.resolve("matmul", "blocks.0.attn.wq").fmt == "e4m3"


@settings(max_examples=50, deadline=None)
@given(
    mfmt=st.sampled_from(["none", "e4m3", "e5m2"]),
    mode=st.sampled_from(["rne", "rz", "rd", "ru", "stochastic"]),
    impl=st.sampled_from(["auto", "xla", "fused_dequant"]),
    accum=st.sampled_from(["f32", "bf16"]),
    kv=st.sampled_from(["none", "e5m2"]),
    static=st.booleans(),
    n_ov=st.integers(min_value=0, max_value=3),
)
def test_json_roundtrip_random_policies(mfmt, mode, impl, accum, kv, static,
                                        n_ov):
    ovs = tuple(
        Override("matmul", f"blocks.*.attn.w{'qkvo'[i]}",
                 OpPolicy(fmt="e4m3", mode=mode, impl=impl, accum=accum))
        for i in range(n_ov)
    )
    p = Policy(
        name="prop",
        matmul=OpPolicy(fmt=mfmt, mode=mode, impl=impl, accum=accum),
        # static_weights / quantized matmuls need a weight format; the
        # constructor enforces it, so satisfy it up front
        weights=OpPolicy(fmt="e4m3" if (mfmt != "none" or static) else "none"),
        kv_write=OpPolicy(fmt=kv, mode=mode),
        static_weights=static,
        overrides=ovs,
    )
    assert Policy.from_json(p.to_json()) == p


def test_registry_unknown_name():
    with pytest.raises(KeyError, match="unknown numerics policy"):
        get_policy("no_such_policy")


def test_shard_specs_roundtrip_and_resolution():
    """Per-site sharding roles (ISSUE 10): JSON round-trip, fnmatch
    last-match-wins resolution, and role validation.  Stacked params'
    sites have no block index ("blocks.attn.wq"), so globs look like
    "blocks.*" / "*.wq", not "blocks.*.attn.wq"."""
    p = Policy(
        name="tp",
        weights=OpPolicy(fmt="e4m3"),
        static_weights=True,
        shard_specs=(("blocks.*", "columns"),
                     ("*.wo", "replicate"),
                     ("embed", "rows")),
    )
    q = Policy.from_json(p.to_json())
    assert q == p and q.shard_specs == p.shard_specs
    assert Policy.from_dict(p.to_dict()) == p
    assert p.resolve_shard("blocks.attn.wq") == "columns"
    assert p.resolve_shard("blocks.attn.wo") == "replicate"  # last match wins
    assert p.resolve_shard("embed") == "rows"
    assert p.resolve_shard("unembed") is None
    with pytest.raises(ValueError, match="role 'diagonal'"):
        Policy(name="bad", shard_specs=(("*", "diagonal"),))


def test_legacy_alias_maps_through_to_policy():
    """Each legacy --quant flag and its preset agree after the
    QuantConfig round trip (the deprecation-alias contract)."""
    for quant, preset in LEGACY_QUANT_PRESETS.items():
        qc = legacy_quant_config(quant)
        pc = get_policy(preset).to_quant_config()
        assert qc.to_policy() == pc.to_policy(), (quant, preset)


# --------------------------------------------------------------------------- #
# Validation at construction (satellite: the mixed-format LNS failure mode)
# --------------------------------------------------------------------------- #
def test_mixed_format_lns_rejected_at_construction():
    with pytest.raises(ValueError, match=r"op-site matmul:<base>"):
        Policy(
            matmul=OpPolicy(fmt="e5m2", impl="lns"),
            weights=OpPolicy(fmt="e4m3"),
        )


def test_mixed_format_lns_override_rejected_with_site_name():
    with pytest.raises(ValueError, match=r"blocks\.\*\.attn\.wq"):
        Policy(
            matmul=OpPolicy(fmt="e4m3", impl="auto"),
            weights=OpPolicy(fmt="e4m3"),
            overrides=(
                Override("matmul", "blocks.*.attn.wq",
                         OpPolicy(fmt="e5m2", impl="lns")),
            ),
        )


def test_legacy_mixed_lns_quantconfig_now_coerces_and_runs():
    """Regression: QuantConfig(enabled=True, matmul_impl='lns') with the
    default e5m2/e4m3 split used to trip an assert deep inside
    _ste_qmatmul tracing; to_policy() coerces it single-format."""
    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_lns_pallas")
    pol = cfg.quant.to_policy()  # explicit: works under the forced-legacy job
    assert pol.matmul.fmt == pol.weights.fmt == "e4m3"
    from repro.models.layers import qlinear

    x = jnp.asarray(np.random.RandomState(0).randn(4, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
    y = qlinear(x, w, pol)
    assert np.isfinite(np.asarray(y)).all()


def test_static_weights_need_weight_format():
    with pytest.raises(ValueError, match="static_weights"):
        Policy(static_weights=True)


def test_mixed_format_lns_via_weights_override_rejected():
    """Regression: a 'weights' override reaching an LNS matmul site must
    be caught at construction too, not at trace time."""
    with pytest.raises(ValueError, match=r"blocks\.\*\.attn\.wq"):
        Policy(
            matmul=OpPolicy(fmt="e4m3", impl="lns"),
            weights=OpPolicy(fmt="e4m3"),
            overrides=(
                Override("weights", "blocks.*.attn.wq",
                         OpPolicy(fmt="e5m2")),
            ),
        )


def test_attention_pv_format_must_match_qk():
    with pytest.raises(ValueError, match="attention_pv"):
        Policy(
            attention_qk=OpPolicy(fmt="e5m2"),
            attention_pv=OpPolicy(fmt="e4m3"),
            kv_write=OpPolicy(fmt="e5m2"),
        )


def test_ste_matmul_honors_accum():
    """accum='f32' vs 'bf16' must reach matmul_q's compute_dtype on the
    STE path (not just the static path)."""
    x = jnp.asarray(np.random.RandomState(0).randn(8, 32), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(32, 8), jnp.float32)

    def pol(accum):
        return Policy(
            matmul=OpPolicy(fmt="e5m2", mode="rne", impl="xla", accum=accum),
            weights=OpPolicy(fmt="e4m3"),
        )

    # each accum request must reach matmul_q's compute_dtype (FP8 decodes
    # exactly into bf16, so xla outputs may coincide numerically — the
    # contract under test is the plumbing, pinned against explicit calls)
    from repro.kernels import ops as kops

    qx = quantize(x, "e5m2")
    qw = quantize(w, "e4m3", axis=-1)
    for accum, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        got = numerics.matmul(x, w, pol(accum))
        ref = kops.matmul_q(qx, qw, impl="xla", compute_dtype=dt)
        assert bit_equal(got, ref), accum


# --------------------------------------------------------------------------- #
# Per-site override resolution
# --------------------------------------------------------------------------- #
def test_resolve_overrides_last_match_wins():
    p = get_policy("train_fp8_attn_e4m3")
    assert p.resolve("matmul", "blocks.0.attn.wq").fmt == "e4m3"
    assert p.resolve("matmul", "blocks.3.attn.wo").fmt == "e4m3"
    assert p.resolve("matmul", "blocks.0.ffn.w_gate").fmt == "e5m2"
    assert p.resolve("matmul", "prefix.1.attn.wk").fmt == "e4m3"
    # stacked overrides: later entries shadow earlier ones
    p2 = Policy(
        matmul=OpPolicy(fmt="e5m2"),
        weights=OpPolicy(fmt="e4m3"),
        overrides=(
            Override("matmul", "blocks.*", OpPolicy(fmt="e4m3")),
            Override("matmul", "blocks.0.attn.*", OpPolicy(fmt="e5m2")),
        ),
    )
    assert p2.resolve("matmul", "blocks.0.ffn.w_up").fmt == "e4m3"
    assert p2.resolve("matmul", "blocks.0.attn.wq").fmt == "e5m2"


# --------------------------------------------------------------------------- #
# QTensor pytree behavior under jit / scan / vmap
# --------------------------------------------------------------------------- #
def _qt(shape=(4, 8), seed=0, fmt="e4m3"):
    x = jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)
    return quantize(x, fmt)


def test_qtensor_jit_through_boundary():
    q = _qt()
    f = jax.jit(lambda t: t.dequantize())
    assert bit_equal(f(q), q.dequantize())  # elementwise: exactly equal
    s = jax.jit(lambda t: t.dequantize().sum())(q)
    np.testing.assert_allclose(  # reductions may reassociate under jit
        np.asarray(s), np.asarray(q.dequantize().sum()), rtol=1e-6
    )
    g = jax.jit(lambda t: QTensor(codes=t.codes, scale=t.scale * 2.0,
                                  fmt=t.fmt))
    out = g(q)
    assert isinstance(out, QTensor) and out.fmt == q.fmt
    assert bit_equal(out.dequantize(), q.dequantize() * 2.0)


def test_qtensor_scan_vmap():
    T = 5
    codes = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (T, 3, 4)), jnp.uint8
    )
    scales = jnp.asarray(np.linspace(0.5, 2.0, T), jnp.float32)
    qs = QTensor(codes=codes, scale=scales.reshape(T, 1, 1), fmt="e5m2")

    def body(carry, qt):
        return carry + qt.dequantize().sum(), qt.dequantize().max()

    total, maxes = jax.lax.scan(body, jnp.float32(0.0), qs)
    ref = sum(
        (decode(codes[t], "e5m2") * scales[t]).sum() for t in range(T)
    )
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref), rtol=1e-6)

    vm = jax.vmap(lambda qt: qt.dequantize().sum())(qs)
    assert vm.shape == (T,)


def test_qtensor_keyed_paths_named_codes_scale():
    """Path-based tooling (checkpoints, sharding rules) must keep seeing
    'codes'/'scale' names, as with the old dict carrier."""
    leaves = jax.tree_util.tree_flatten_with_path({"wq": _qt()})[0]
    names = {
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for path, _ in leaves
    }
    assert names == {"wq/codes", "wq/scale"}


def test_page_qtensor_view_shares_decode_path():
    from repro.kernels.common import code_to_f32
    from repro.serving.page_pool import page_qtensor

    from repro.core.quant import encode

    P, page, KV, hd = 3, 4, 2, 8
    # pages hold encoder-produced codes (normals/zeros), as in production —
    # the LUT and bit-placement decodes only diverge on subnormal/NaN
    # codes, which the cache encoder never emits
    pages = encode(
        jnp.asarray(np.random.RandomState(0).randn(P, page, KV, hd) * 4,
                    jnp.float32), "e5m2",
    )
    scales = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
    view = page_qtensor(pages, scales, "e5m2")
    assert isinstance(view, QTensor) and view.shape == pages.shape
    ref = np.asarray(code_to_f32(pages, "e5m2")) * np.asarray(scales).reshape(
        P, 1, 1, 1
    )
    # == treats -0.0 (LUT) and +0.0 (bit placement) as equal
    assert np.array_equal(np.asarray(view.dequantize()), ref)


# --------------------------------------------------------------------------- #
# Bit-identity: legacy QuantConfig string path == policy-resolved path
# --------------------------------------------------------------------------- #
def _all_code_values(fmt):
    """Finite float values of every code of ``fmt`` (NaN codes dropped)."""
    v = np.asarray(decode(jnp.arange(256, dtype=jnp.uint8), fmt))
    return v[np.isfinite(v)]


@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
@pytest.mark.parametrize("mode", ["rne", "rz"])
@pytest.mark.parametrize("impl", ["xla", "lns", "fused_dequant"])
def test_static_matmul_bit_identity_legacy_vs_policy(fmt, mode, impl):
    """static_qmatmul: QuantConfig strings vs the equivalent policy, over
    an operand grid covering every finite code value of the format."""
    from repro.models.quantize import static_qmatmul

    vals = _all_code_values(fmt)
    M = 16
    K = len(vals) // M * M
    x2d = jnp.asarray(vals[:K].reshape(M, K // M), jnp.float32)
    w = jnp.asarray(
        np.random.RandomState(0).permutation(vals)[: (K // M) * 8]
        .reshape(K // M, 8),
        jnp.float32,
    )
    qw = quantize(w, fmt)
    qc = QuantConfig(enabled=True, act_quant=True, act_fmt=fmt,
                     weight_fmt=fmt, mode=mode, matmul_impl=impl)
    legacy = static_qmatmul(x2d, qw, qc)
    policy = static_qmatmul(x2d, qw, qc.to_policy())
    assert bit_equal(legacy, policy)
    # the functional API resolves to the same kernel call
    api = numerics.matmul(x2d, qw, qc.to_policy())
    assert bit_equal(legacy, np.asarray(api, np.float32))


@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
@pytest.mark.parametrize("quant", ["fp8_lns", "fp8_lns_pallas",
                                   "fp8_w8_train"])
def test_ste_qlinear_bit_identity_legacy_vs_policy(fmt, quant):
    """qlinear on float weights: preserved QuantConfig body vs
    numerics.matmul with the mapped policy."""
    from repro.models.layers import _qlinear_legacy

    qc = legacy_quant_config(quant)
    qc = QuantConfig(**{**qc.__dict__, "act_fmt": fmt})
    if qc.matmul_impl in ("lns", "lns_loop") and fmt != qc.weight_fmt:
        # the legacy string path crashes on this combo (the old failure
        # mode; coercion covered by the regression test above)
        pytest.skip("mixed-format LNS: legacy path never worked")
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)
    legacy = _qlinear_legacy(x, w, qc)
    policy = numerics.matmul(x, w, qc.to_policy())
    assert bit_equal(legacy, policy)


@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
@pytest.mark.parametrize("mode", ["rne", "rnz", "rd", "ru", "rz"])
@pytest.mark.parametrize("op", ["mul", "square", "rsqrt"])
def test_elementwise_bit_identity_exhaustive(fmt, mode, op):
    """The gated-MLP elementwise chain, legacy strings vs policy, on the
    exhaustive grid of finite code values (every operand pair for mul)."""
    from repro.core.carry_ins import CARRY_INS
    from repro.core.quant import quantize as q
    from repro.kernels import ops as kops

    if CARRY_INS[(fmt, op)].get(mode) is None:
        pytest.skip(f"{fmt}/{op}/{mode}: no integer expression (paper dash)")
    vals = _all_code_values(fmt)
    if op == "mul":
        xg, yg = np.meshgrid(vals, vals, indexing="ij")
        x, y = jnp.asarray(xg.ravel()), jnp.asarray(yg.ravel())
    else:
        x, y = jnp.asarray(np.abs(vals) + 1e-3), None

    # legacy chain (what gated_mlp used to inline, strings threaded)
    qx = q(x, fmt)
    qy = None if y is None else q(y, fmt)
    legacy = kops.elementwise_q(op, qx, qy, mode=mode).dequantize()

    pol = Policy(
        matmul=OpPolicy(fmt=fmt, mode="rne", impl="auto", accum="bf16"),
        weights=OpPolicy(fmt="e4m3"),
        elementwise=OpPolicy(fmt=fmt, mode=mode, impl="pallas"),
    )
    policy = numerics.elementwise(op, x, y, pol)
    assert bit_equal(legacy, policy)


@pytest.mark.parametrize("with_key", [False, True])
def test_kv_write_bit_identity_legacy_vs_policy(with_key):
    """Paged KV token writes + prefill splices: QuantConfig vs policy."""
    qc = legacy_quant_config("fp8_w8kv8")
    pol = qc.to_policy()
    rng = np.random.RandomState(0)
    P, page, KV, hd = 4, 4, 2, 8
    pages = jnp.zeros((P, page, KV, hd), jnp.uint8)
    scales = jnp.ones((P,), jnp.float32)
    new = jnp.asarray(rng.randn(3, KV, hd), jnp.float32)
    page_ids = jnp.asarray([1, 2, 3], jnp.int32)
    rows = jnp.asarray([0, 1, 3], jnp.int32)
    key = jax.random.PRNGKey(7) if with_key else None
    a = numerics.kv_write_token(qc, pages, scales, new, page_ids, rows,
                                key=key)
    b = numerics.kv_write_token(pol, pages, scales, new, page_ids, rows,
                                key=key)
    assert all(bit_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
               for x, y in zip(a, b))

    src = jnp.asarray(rng.randint(0, 256, (page * 2, KV, hd)), jnp.uint8)
    pids = jnp.asarray([2, 3], jnp.int32)
    c = numerics.kv_write_prefill(qc, pages, scales, src, pids, key=key)
    d = numerics.kv_write_prefill(pol, pages, scales, src, pids, key=key)
    assert all(bit_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
               for x, y in zip(c, d))

    # dense-cache store/load
    x = jnp.asarray(rng.randn(2, 1, KV, hd), jnp.float32)
    assert np.array_equal(np.asarray(numerics.kv_encode(x, qc)),
                          np.asarray(numerics.kv_encode(x, pol)))


def test_kv_encode_dense_bit_identity_nondefault_mode():
    """Regression: the dense store always encoded RNE regardless of
    QuantConfig.mode; the policy mapping must preserve that exactly."""
    qc = QuantConfig(kv_cache_fp8=True, mode="rz")
    x = jnp.asarray(np.linspace(-3.0, 3.0, 64, dtype=np.float32))
    assert np.array_equal(np.asarray(numerics.kv_encode(x, qc)),
                          np.asarray(numerics.kv_encode(x, qc.to_policy())))


def test_legacy_dict_weight_through_qlinear_static_path():
    """Regression: the preserved QuantConfig body must still accept the
    old {"codes","scale"} dict carrier on the static act-quant path."""
    from repro.models.layers import _qlinear_legacy

    rng = np.random.RandomState(0)
    w = quantize(jnp.asarray(rng.randn(16, 8), jnp.float32), "e4m3")
    legacy_w = {"codes": w.codes, "scale": w.scale}
    qc = QuantConfig(enabled=True, act_quant=True)
    x = jnp.asarray(rng.randn(2, 3, 16), jnp.float32)
    assert bit_equal(_qlinear_legacy(x, legacy_w, qc),
                     _qlinear_legacy(x, w, qc))


def test_resolve_weight_dict_honors_configured_format():
    """Regression: legacy e5m2 dict weights must decode as e5m2 at the
    mla/unembed call sites (the policy supplies the format)."""
    from repro.models.quantize import resolve_weight

    w = quantize(jnp.asarray(np.random.RandomState(0).randn(8, 4),
                             jnp.float32), "e5m2")
    legacy_w = {"codes": w.codes, "scale": w.scale}
    qc = QuantConfig(enabled=True, weight_fmt="e5m2")
    fmt = numerics.weight_format(qc.to_policy())
    assert fmt == "e5m2"
    assert bit_equal(resolve_weight(legacy_w, fmt, jnp.float32),
                     resolve_weight(w, dtype=jnp.float32))


# --------------------------------------------------------------------------- #
# Greedy serving bit-identity per preset (the acceptance headline)
# --------------------------------------------------------------------------- #
def _serve_outputs(cfg, scheduler="bucketed", cache_impl="paged"):
    from repro.launch import serve

    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab, size=l) for l in (4, 9, 6)]
    eng = serve.Engine(cfg, slots=2, max_seq=24, cache_impl=cache_impl,
                       page_size=8, rng_seed=0)
    outputs, _ = serve.run(eng, queue, gen=6, quiet=True,
                           scheduler=scheduler)
    return outputs


@pytest.mark.parametrize("quant,preset", [
    ("fp8_w8kv8", "serve_fp8_paged"),
    ("fp8_w8", "weight_only_e4m3"),
    ("none", "train_bf16"),
])
def test_greedy_serving_identical_legacy_flag_vs_preset(quant, preset):
    cfg_q = get_config("qwen2-0.5b", smoke=True, quant=quant)
    cfg_p = get_config("qwen2-0.5b", smoke=True, policy=preset)
    impl = "paged" if quant == "fp8_w8kv8" else "dense"
    out_q = _serve_outputs(cfg_q, cache_impl=impl)
    out_p = _serve_outputs(cfg_p, cache_impl=impl)
    assert out_q == out_p


def test_greedy_serving_identical_continuous():
    cfg_q = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    cfg_p = get_config("qwen2-0.5b", smoke=True, policy="serve_fp8_paged")
    out_q = _serve_outputs(cfg_q, scheduler="continuous")
    out_p = _serve_outputs(cfg_p, scheduler="continuous")
    assert out_q == out_p


def test_forced_legacy_env_is_bit_identical(monkeypatch):
    """REPRO_FORCE_LEGACY_QUANTCONFIG=1 re-routes cfg.policy onto the
    preserved QuantConfig string paths; serving outputs must not move."""
    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    monkeypatch.delenv("REPRO_FORCE_LEGACY_QUANTCONFIG", raising=False)
    assert isinstance(cfg.policy, Policy)
    out_new = _serve_outputs(cfg)
    monkeypatch.setenv("REPRO_FORCE_LEGACY_QUANTCONFIG", "1")
    assert isinstance(cfg.policy, QuantConfig)
    out_old = _serve_outputs(cfg)
    assert out_new == out_old


# --------------------------------------------------------------------------- #
# Model layers never pass numeric strings (the CI lint, as a test)
# --------------------------------------------------------------------------- #
def test_models_pass_no_numeric_string_kwargs():
    sys.path.insert(
        0, str(pathlib.Path(__file__).resolve().parents[1] / "scripts")
    )
    import lint_numerics

    assert lint_numerics.violations() == []
