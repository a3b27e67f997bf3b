"""Paged KV-cache serving subsystem tests.

Covers: the Pallas paged decode-attention kernel's bit-identity with its
pure-JAX reference (the subsystem's numerics contract), the page pool
allocator, page write/splice quantization, the per-slot position vector
decode path, and end-to-end paged-vs-dense engine agreement.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import encode
from repro.kernels.common import code_to_f32
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import paged_decode_attention
from repro.serving import (
    PagePool,
    pow2_page_scale,
    rescale_codes,
    write_prefill_pages,
    write_token_page,
)


def _paged_inputs(seed, *, B=3, H=4, KV=2, hd=16, page=8, P=12, maxp=5):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
    kf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    ks = jnp.asarray((0.5 + rng.random(P)).astype(np.float32))
    vs = jnp.asarray((0.5 + rng.random(P)).astype(np.float32))
    kp = encode(jnp.asarray(kf), "e5m2")
    vp = encode(jnp.asarray(vf), "e5m2")
    bt = jnp.asarray(
        np.array([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0], [8, 9, 10, 0, 0]], np.int32)
    )
    lengths = jnp.asarray(
        np.array([int(rng.integers(1, maxp * page + 1)), 12, 17], np.int32)
    )
    return q, kf, vf, kp, vp, ks, vs, bt, lengths


def _page_edge_inputs(seed, *, B=5, H=4, KV=2, hd=16, page=8, maxp=5):
    """Every slot owns ``maxp`` pages; the attend lengths sit at page
    edges (1, page, page+1, maxp*page-1, maxp*page), mixed across slots."""
    rng = np.random.default_rng(seed)
    P = B * maxp + 1
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
    kf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    ks = jnp.asarray((0.5 + rng.random(P)).astype(np.float32))
    vs = jnp.asarray((0.5 + rng.random(P)).astype(np.float32))
    bt = rng.permutation(np.arange(1, P)).reshape(B, maxp).astype(np.int32)
    edges = [1, page, page + 1, maxp * page - 1, maxp * page]
    lengths = rng.permutation(np.resize(edges, B)).astype(np.int32)
    return (q, kf, vf, encode(jnp.asarray(kf), "e5m2"),
            encode(jnp.asarray(vf), "e5m2"), ks, vs, jnp.asarray(bt),
            jnp.asarray(lengths))


def _mla_inputs(make, seed, *, H=4, dr=8, L=32):
    """MLA-mode operands over ``make``'s block tables and lengths: query
    ``[q_pe ‖ q_eff]`` [B, 1, H, dr + L], rope-key pages [P, page, dr] and
    latent pages [P, page, L] as E5M2 codes, with per-page scales."""
    _, kf, _, _, _, ks, vs, bt, lengths = make(seed)
    P, page = kf.shape[:2]
    rng = np.random.default_rng(1000 + seed)
    q = jnp.asarray(rng.standard_normal((bt.shape[0], 1, H, dr + L))
                    .astype(np.float32))
    kpe = rng.standard_normal((P, page, dr)).astype(np.float32)
    ckv = rng.standard_normal((P, page, L)).astype(np.float32)
    return (q, kpe, ckv, encode(jnp.asarray(kpe), "e5m2"),
            encode(jnp.asarray(ckv), "e5m2"), ks, vs, bt, lengths)


# --------------------------------------------------------------------------- #
# Kernel == reference == batch, bit for bit (the acceptance contract)
# --------------------------------------------------------------------------- #
def _partials_jit(fn, *static):
    """``fn`` jitted once per test process, so the seeds of a case share
    its compiles."""
    return jax.jit(fn, static_argnames=static + (
        "fmt", "mode", "KV", "G", "window", "cap", "scale"))


_batch_partials_jit = _partials_jit(pa._batch_partials)
_kernel_partials_jit = _partials_jit(pa._paged_kernel_partials, "interpret",
                                     "page_size", "ppb", "spb")


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize(
    "kw", [dict(), dict(window=7, cap=25.0), dict(mode="faithful"),
           dict(edges=True), dict(edges=True, window=7, cap=25.0),
           dict(mla=True), dict(mla=True, edges=True)],
    ids=["plain", "window-cap", "faithful", "page-edges",
         "page-edges-window-cap", "mla", "mla-page-edges"],
)
def test_paged_lns_kernel_bit_identical_to_ref(seed, kw):
    """kernel == ref == batch, unfused and fused (the token written at
    ``lengths - 1``).  Where the case's lengths are the drawn or page-edge
    ones (not ``window-cap`` / ``faithful``), also at every (pages per
    block, slots per program) below: the kernel's folded partials equal
    the batch's, one per slot, for the case's lengths and for lengths that
    hold a slot of length 0 (output 0, not NaN) and slots whose live pages
    end on a page edge; and the outputs of the three impls agree, fused
    and unfused.  The ``mla``
    cases run the kernel's MLA mode: one KV head, a query of rope and
    latent lanes against rope-key and latent pages, the latents as
    values."""
    kw = dict(kw)
    edges = kw.pop("edges", False)
    make = _page_edge_inputs if edges else _paged_inputs
    KV = 2
    if kw.pop("mla", False):
        KV, kw["scale"] = 1, 0.3
        make = functools.partial(_mla_inputs, make)
    q, kf, vf, kp, vp, ks, vs, bt, lengths = make(seed)
    args = (q, kp, vp, ks, vs, bt, lengths)
    outs = [paged_decode_attention(*args, fmt="e5m2", n_kv_heads=KV,
                                   impl=impl, interpret=True, **kw)
            for impl in ("ref", "batch", "kernel")]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))

    B, page = q.shape[0], kp.shape[1]
    rng = np.random.default_rng(100 + seed)
    new = [jnp.asarray(rng.standard_normal((B, KV, d)).astype(np.float32))
           for d in (kp.shape[-1], vp.shape[-1])]
    fused = [pa.fused_decode_write_attend(
        q, *new, kp, vp, ks, vs, bt, lengths - 1, fmt="e5m2", n_kv_heads=KV,
        kv_mode="rne", impl=impl, interpret=True, **kw)
        for impl in ("ref", "batch", "kernel")]
    for f in fused[1:]:
        for a, b in zip(fused[0], f):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not edges and ("window" in kw or "mode" in kw):
        return

    geom = dict(fmt="e5m2", mode=kw.get("mode", "rne"),
                window=kw.get("window", 0), cap=kw.get("cap", 0.0),
                scale=kw.get("scale"))

    @functools.cache
    def attend(impl, fused, ppb=1, spb=1):
        """The (fused: written caches and) output of ``impl``; ref and
        batch take no block shape."""
        blocks = dict(ppb=ppb, spb=spb, n_kv_heads=KV, impl=impl,
                      interpret=True, **geom)
        if fused:
            return pa._fused_decode_write_attend(
                q, *new, kp, vp, ks, vs, bt, lengths - 1, None, None, None,
                kv_mode="rne", **blocks)[:5]
        return (pa._paged_decode_attention(q, kp, vp, ks, vs, bt, edge_lens,
                                           **blocks),)

    # a slot of length 0, slots whose live pages end on a page edge, and
    # one a row past an edge
    edge_lens = jnp.asarray(np.resize([0, 2 * page, 4 * page, page + 1], B),
                            jnp.int32)
    codes, qs = pa.quantize_q(q.reshape(B, 4, -1), "e5m2")
    G = 4 // KV
    # one slot a program, and padded blocks of 2 and 3 slots
    for ppb, spb in ((1, 1), (4, 1), (2, 2), (3, 3)):
        for lens in (lengths, edge_lens):
            want = _batch_partials_jit(
                (codes, qs), kp, vp, ks, vs, bt, lens, KV=KV, G=G, **geom)
            got = _kernel_partials_jit(
                codes, qs, kp, vp, ks, vs, bt, lens, page_size=page, KV=KV,
                G=G, interpret=True, ppb=ppb, spb=spb, **geom)
            assert got[0].shape == (B, KV, G)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
        for fused in (False, True):
            want = attend("ref", fused)
            for got in (attend("batch", fused),
                        attend("kernel", fused, ppb, spb)):
                for w, g in zip(want, got):
                    np.testing.assert_array_equal(np.asarray(w),
                                                  np.asarray(g))
    out = np.asarray(attend("ref", False)[0])
    assert np.all(np.isfinite(out))
    assert np.all(out[np.asarray(edge_lens) == 0] == 0)


@pytest.mark.parametrize("shape", ["gqa", "mla"])
def test_kernel_emits_one_partial_per_slot_at_decode_long(shape):
    """At decode-long's geometry (64 slots x 96 pages) the kernel emits
    one folded partial per slot, [B, KV, G(, dv)], not one per page of
    ``max_seq`` (shapes only: the launch is traced, not run)."""

    B, maxp, page = 64, 96, 16
    KV, G, hd, dv = (2, 7, 64, 64) if shape == "gqa" else (1, 16, 576, 512)
    dk = hd if shape == "gqa" else hd - dv
    P = B * maxp + 1
    S = jax.ShapeDtypeStruct
    out = jax.eval_shape(
        functools.partial(
            pa._paged_kernel_partials, fmt="e5m2", mode="rne",
            page_size=page, KV=KV, G=G, window=0, cap=0.0, interpret=True,
            ppb=4),
        S((B, KV * G, hd), jnp.uint8), S((B,), jnp.float32),
        S((P, page, KV, dk) if shape == "gqa" else (P, page, dk), jnp.uint8),
        S((P, page, KV, dv) if shape == "gqa" else (P, page, dv), jnp.uint8),
        S((P,), jnp.float32), S((P,), jnp.float32),
        S((B, maxp), jnp.int32), S((B,), jnp.int32))
    assert [o.shape for o in out] == [(B, KV, G), (B, KV, G), (B, KV, G, dv)]


@pytest.mark.parametrize("KV,G,dv", [(2, 7, 64), (1, 7, 64), (8, 5, 128)],
                         ids=["qwen2", "qwen2-tp2-shard", "qwen3-14b"])
def test_kernel_partials_vmem_does_not_grow_with_max_seq(KV, G, dv):
    """A kernel program's output block is one folded partial per slot,
    ``[spb, KV, G(, dv)]``, whatever ``maxp``: a longer ``max_seq``
    takes more pages walked, not more VMEM."""

    P, page, hd = 8, 16, dv
    S = jax.ShapeDtypeStruct
    for spb in (1, 4):
        for maxp in (1, 96, 97, 2048):
            shapes = jax.eval_shape(
                functools.partial(
                    pa._paged_pallas, n_prefetch=5, fmt="e5m2", mode="rne",
                    page_size=page, window=0, cap=0.0, interpret=True,
                    ppb=1, spb=spb, scale=None),
                S((spb, maxp), jnp.int32), S((spb,), jnp.int32),
                S((spb,), jnp.float32), S((spb, maxp), jnp.float32),
                S((spb, maxp), jnp.float32), S((spb, KV, G, hd), jnp.uint8),
                S((P, page, KV, hd), jnp.uint8),
                S((P, page, KV, dv), jnp.uint8))
            assert [s.shape for s in shapes] == [
                (spb, KV, G), (spb, KV, G), (spb, KV, G, dv)]


@pytest.mark.parametrize("seed", range(2))
def test_paged_float_kernel_bit_identical_to_ref(seed):
    q, kf, vf, kp, vp, ks, vs, bt, lengths = _paged_inputs(seed)
    one = jnp.ones_like(ks)
    args = (q, jnp.asarray(kf), jnp.asarray(vf), one, one, bt, lengths)
    o_ref = paged_decode_attention(*args, fmt=None, n_kv_heads=2, impl="ref")
    o_ker = paged_decode_attention(*args, fmt=None, n_kv_heads=2,
                                   impl="kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_ker))


def test_paged_float_matches_dense_decode_attention():
    """Gathering pages == a contiguous dense cache, same math."""
    from repro.models.layers import decode_attention

    q, kf, vf, *_ = _paged_inputs(5, B=1)
    bt = jnp.asarray(np.array([[1, 2, 3, 4]], np.int32))
    L = 29
    one = jnp.ones(12, jnp.float32)
    out_p = paged_decode_attention(
        q, jnp.asarray(kf), jnp.asarray(vf), one, one, bt,
        jnp.asarray([L]), fmt=None, n_kv_heads=2, impl="ref",
    )
    k_d = jnp.asarray(kf[np.asarray(bt)[0]].reshape(1, -1, 2, 16))
    v_d = jnp.asarray(vf[np.asarray(bt)[0]].reshape(1, -1, 2, 16))
    out_d = decode_attention(q, k_d, v_d, pos=L - 1)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


def test_paged_lns_matches_float_within_quant_tolerance():
    """The integer-domain QK^T path tracks the f32 path to FP8 accuracy."""
    q, kf, vf, kp, vp, ks, vs, bt, lengths = _paged_inputs(3)
    one = jnp.ones_like(ks)
    o_lns = paged_decode_attention(q, kp, vp, one, one, bt, lengths,
                                   fmt="e5m2", n_kv_heads=2, impl="ref")
    # float path over the DECODED codes isolates the q-quantization +
    # integer-product error from the kv quantization error
    kd = code_to_f32(kp, "e5m2")
    vd = code_to_f32(vp, "e5m2")
    o_f32 = paged_decode_attention(q, kd, vd, one, one, bt, lengths,
                                   fmt=None, n_kv_heads=2, impl="ref")
    err = np.abs(np.asarray(o_lns) - np.asarray(o_f32))
    assert np.median(err) < 0.15, np.median(err)


# --------------------------------------------------------------------------- #
# Page pool
# --------------------------------------------------------------------------- #
def test_page_pool_alloc_free_cycle():
    pool = PagePool(num_pages=8, page_size=4, slots=2, max_pages_per_slot=4)
    assert pool.free_pages == 7  # page 0 reserved
    a = pool.alloc(0, 3)
    assert len(set(a)) == 3 and 0 not in a
    assert pool.block_tables[0, :3].tolist() == a
    b = pool.alloc(1, 4)
    assert not pool.can_alloc(1)
    with pytest.raises(RuntimeError):
        pool.alloc(0, 1)
    pool.free_slot(1)
    assert pool.free_pages == 4
    assert pool.block_tables[1].tolist() == [0, 0, 0, 0]
    assert sorted(pool._free[-4:]) == sorted(b)
    pool.ensure_capacity(0, 13)  # 13 tokens -> 4 pages
    assert len(pool.pages_of[0]) == 4


def test_page_pool_respects_max_pages_per_slot():
    pool = PagePool(num_pages=16, page_size=4, slots=1, max_pages_per_slot=2)
    pool.alloc(0, 2)
    with pytest.raises(RuntimeError):
        pool.alloc(0, 1)


# --------------------------------------------------------------------------- #
# Page writes: pow2 scales, stochastic rounding
# --------------------------------------------------------------------------- #
def test_pow2_page_scale_is_pow2_and_covers():
    amax = jnp.asarray([1e-9, 0.3, 7.0, 3e4], jnp.float32)
    s = np.asarray(pow2_page_scale(amax, "e5m2"))
    assert np.all(np.exp2(np.round(np.log2(s))) == s)  # powers of two
    # amax / s fits in the format (no saturation beyond one rounding step)
    assert np.all(np.asarray(amax) / s <= 57344.0 + 1e-3)


def test_prefill_splice_pow2_rescale_is_exact():
    """Scale-1 codes -> pow2-scaled pages loses NO information."""
    rng = np.random.default_rng(0)
    P, page, KV, hd = 5, 4, 2, 8
    pages = jnp.zeros((P, page, KV, hd), jnp.uint8)
    scales = jnp.ones((P,), jnp.float32)
    src = encode(jnp.asarray(rng.standard_normal((7, KV, hd)).astype(np.float32) * 3),
                 "e5m2")
    pages, scales = write_prefill_pages(
        pages, scales, src, jnp.asarray([2, 4]), fmt="e5m2",
        key=jax.random.PRNGKey(0),
    )
    got = np.concatenate([
        np.asarray(code_to_f32(pages[2], "e5m2")) * float(scales[2]),
        np.asarray(code_to_f32(pages[4], "e5m2")) * float(scales[4]),
    ])[:7]
    want = np.asarray(code_to_f32(src, "e5m2"))
    np.testing.assert_array_equal(got, want)


def test_rescale_codes_stochastic_is_faithful():
    """Non-pow2 ratios: stochastic carry-in rescale stays within one ulp."""
    codes = encode(jnp.asarray(np.linspace(0.1, 100, 256).astype(np.float32)),
                   "e5m2")
    r = rescale_codes(codes, 1 / 3.0, "e5m2", key=jax.random.PRNGKey(1))
    got = np.asarray(code_to_f32(r, "e5m2"))
    want = np.asarray(code_to_f32(codes, "e5m2")) / 3.0
    rel = np.abs(got - want) / want
    assert rel.max() < 0.25 + 1e-6  # one e5m2 mantissa step


def test_write_token_page_fresh_page_sets_scale():
    rng = np.random.default_rng(1)
    P, page, KV, hd = 4, 4, 2, 8
    pages = jnp.zeros((P, page, KV, hd), jnp.uint8)
    scales = jnp.ones((P,), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, KV, hd)).astype(np.float32) * 5)
    pages, scales = write_token_page(
        pages, scales, new, jnp.asarray([1, 2]), jnp.asarray([0, 2]),
        fmt="e5m2", key=jax.random.PRNGKey(0),
    )
    # row-0 write (slot 0) claimed page 1 and set a pow2 scale
    s1 = float(scales[1])
    assert s1 != 1.0 and np.exp2(np.round(np.log2(s1))) == s1
    got = np.asarray(code_to_f32(pages[1, 0], "e5m2")) * s1
    rel = np.abs(got - np.asarray(new[0])) / (np.abs(np.asarray(new[0])) + 1e-6)
    assert np.median(rel) < 0.2
    # row-2 write (slot 1) reused page 2's existing scale
    assert float(scales[2]) == 1.0


# --------------------------------------------------------------------------- #
# Per-slot positions + end-to-end engines
# --------------------------------------------------------------------------- #
def test_decode_step_accepts_position_vector():
    """Staggered per-slot decode == each sequence decoded alone."""
    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config("qwen2-0.5b", smoke=True)
    m = Model(cfg, max_seq=12)
    params = m.init(jax.random.PRNGKey(0))
    step = jax.jit(m.decode_step)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 256, (2, 12)), jnp.int32)

    # joint decode: slot 0 starts at position 0, slot 1 at position 4
    cache = m.make_cache(2, 12)
    offs = np.array([0, 4])
    joint = []
    for t in range(8):
        l, cache = step(params, cache, toks[:, t], jnp.asarray(offs + t))
        joint.append(np.asarray(l))

    # each slot alone at its own positions
    for b in range(2):
        cache1 = m.make_cache(1, 12)
        for t in range(8):
            l1, cache1 = step(params, cache1, toks[b:b + 1, t],
                              jnp.asarray(offs[b:b + 1] + t))
            np.testing.assert_allclose(joint[t][b], np.asarray(l1)[0],
                                       rtol=2e-3, atol=2e-3)


def test_paged_engine_matches_dense_engine():
    """End-to-end: greedy outputs agree between cache backends, and the
    paged engine admits mixed-length prompts."""
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab, size=4 + 3 * (i % 2)) for i in range(5)]
    outs = {}
    for impl in ("dense", "paged"):
        eng = serve.Engine(cfg, slots=3, max_seq=15, cache_impl=impl,
                           page_size=4)
        outs[impl], stats = serve.run(eng, [q.copy() for q in queue], gen=6,
                                      quiet=True)
        assert stats["steps"] > 0
    assert len(outs["paged"]) == 5
    assert outs["dense"] == outs["paged"]


def test_paged_engine_reuses_freed_pages():
    """A pool smaller than worst case serves all requests via page reuse."""
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    rng = np.random.default_rng(1)
    queue = [rng.integers(0, cfg.vocab, size=4) for _ in range(4)]
    # worst case would need slots * ceil(10/4) = 6 pages; give it 4 (+null)
    eng = serve.Engine(cfg, slots=2, max_seq=10, cache_impl="paged",
                       page_size=4, num_pages=5)
    outs, _ = serve.run(eng, queue, gen=6, quiet=True)
    assert len(outs) == 4
    assert eng.pool.free_pages == 4  # everything released


def test_host_transfers_pinned_one_per_allocating_step():
    """Block-table uploads are batched per STEP, not per slot: exactly one
    ``host_transfers_total`` increment on any step that changes the block
    tables (even when every slot allocates a page simultaneously), and
    zero on steady-state in-page decode steps, which reuse the engine's
    cached device copy."""
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    eng = serve.Engine(cfg, slots=2, max_seq=16, cache_impl="paged",
                       page_size=4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=3) for _ in range(2)]

    def transfers():
        return eng.tel.counter_value("host_transfers_total")

    # one chunked-prefill step allocates a page for BOTH slots: one upload
    eng.tail_prefill([(s, p, 0) for s, p in enumerate(prompts)])
    assert transfers() == 1

    lengths = np.array([3, 3], np.int32)
    for _ in range(6):
        owned = len(eng.pool.pages_of[0])
        # both slots cross the same page boundary on the same step — a
        # single batched upload must cover them
        allocating = -(-(int(lengths[0]) + 1) // 4) > owned
        before = transfers()
        toks = rng.integers(0, cfg.vocab, size=(2,)).astype(np.int32)
        eng.decode_paged(toks, lengths)
        assert transfers() - before == (1 if allocating else 0), lengths
        lengths += 1

    # scheduler-level bound: a full run never uploads more than once per
    # step (and skips the upload on most steady-state decode steps)
    eng2 = serve.Engine(cfg, slots=2, max_seq=16, cache_impl="paged",
                        page_size=4)
    queue = [rng.integers(0, cfg.vocab, size=4) for _ in range(3)]
    _, stats = serve.run(eng2, queue, gen=6, quiet=True,
                         scheduler="continuous")
    n = eng2.tel.counter_value("host_transfers_total")
    assert 0 < n <= stats["steps"]


@pytest.mark.parametrize("scheduler", ["bucketed", "continuous"])
def test_paged_attn_pages_counter_matches_lengths(scheduler):
    """``paged_attn_pages_total{walked, grid}`` equal the sums reckoned
    from the lengths of every model call of a small engine run: walked =
    layers x the pages holding a row of each sub-step's attend length
    (at least one), grid = layers x sub-steps x slots x max pages."""
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_w8kv8")
    eng = serve.Engine(cfg, slots=3, max_seq=24, cache_impl="paged",
                       page_size=4)
    attends = []
    decode, chunk = eng.decode_paged, eng.step_chunk

    def decode_paged(tokens, lengths, **kw):
        attends.append(np.asarray(lengths)[None] + 1)
        return decode(tokens, lengths, **kw)

    def step_chunk(tokens, lengths, n_new, **kw):
        # sub-step t of a slot attends to its position lengths + t (held
        # at its last valid token once t passes n_new), plus one
        attends.append(np.array(
            [[int(n0) + min(t, max(int(n) - 1, 0)) + 1
              for n0, n in zip(lengths, n_new)]
             for t in range(np.shape(tokens)[1])]))
        return chunk(tokens, lengths, n_new, **kw)

    eng.decode_paged, eng.step_chunk = decode_paged, step_chunk
    rng = np.random.default_rng(4)
    queue = [rng.integers(0, cfg.vocab, size=3 + 2 * i) for i in range(4)]
    serve.run(eng, queue, gen=7, quiet=True, scheduler=scheduler)
    assert attends
    layers, maxp = cfg.n_layers, 24 // 4
    walked = sum(int(np.maximum(-(-a // 4), 1).sum()) for a in attends)
    grid = sum(a.size for a in attends) * maxp
    count = eng.tel.counter_value
    assert count("paged_attn_pages_total", kind="walked") == layers * walked
    assert count("paged_attn_pages_total", kind="grid") == layers * grid
    assert walked < grid
