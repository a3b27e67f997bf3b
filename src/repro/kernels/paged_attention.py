"""Pallas TPU paged decode attention with integer-domain (LNS) QK^T.

The serving subsystem stores the KV cache as fixed-size pages of raw FP8
codes plus one f32 scale per page (``repro.serving.page_pool``).  This
kernel consumes that layout directly: for each batch slot it visits the
slot's block-table pages, computes the q·k dot products **in the paper's
LNS integer domain** — code add + Table-2/3 carry-in via the shared
``lns_prepare``/``lns_combine`` machinery from ``kernels.common`` — and
decodes to float32 only for the softmax / PV stage.  The FP8 codes are what
crosses HBM: at 1 byte/elem + one scale per page, decode-attention HBM
traffic is ~half of a bf16 cache, and no float multiplier touches the QK^T
products.

Structure: flash-decoding style two-phase split.  Phase 1 (the Pallas
kernel, one program per block of slots) folds the softmax partials (m, l,
unnormalized o) of the pages it walks into one running partial per slot,
online and in walk order.  Each program walks only the pages of its slots
that hold a row: block tables and per-slot lengths ride in as
scalar-prefetch operands, the K/V pools stay in HBM, and each live page is
gathered by its own DMA (``bt[b, j]``), double-buffered behind the
previous block's compute.  A page with no valid row (past a slot's
length, or outside its window) has the partial (NEG_INF, 0, 0), which the
fold takes as an exact identity: the kernel skips it, the references fold
it.  Phase 2 (plain jnp, shared verbatim by the kernel wrapper and the
pure-JAX references) divides each slot's ``o`` by its ``l``.

Numerics contract: ``impl="kernel"`` (interpret on CPU) and ``impl="batch"``
(natively vectorized phase 1, the CPU serving path) are bit-identical to
``impl="ref"`` — all three run the same per-element LNS ops, the same
fold order and the same phase 2, and every order-sensitive f32 reduction
is pinned behind ``jax.lax.optimization_barrier`` so XLA cannot
re-vectorize or FMA-contract one side differently
(``tests/test_paged_serving.py`` and ``tests/test_paged_fuzz.py`` pin
this).

``fused_decode_write_attend`` is the decode hot path's single entry: it
computes the new token's page codes once, scatters them into the cache
arrays for the *next* step, and attends **without reading the scattered
arrays** — the freshly encoded row is inserted into the gathered page block
in-flight (in-kernel for ``impl="kernel"``), so the attention never
serializes behind the O(P·page·KV·hd) cache update.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import FORMATS
from ..core.quant import encode
from .common import code_to_f32, lns_combine, lns_prepare

NEG_INF = -2.0e30


# --------------------------------------------------------------------------- #
# Q quantization (shared by kernel, reference and tests so the paged paths
# agree bit-for-bit on the quantized query).
# --------------------------------------------------------------------------- #
def quantize_q(q, fmt: str, mode: str = "rne"):
    """[B, H, hd] float -> (codes [B, H, hd] uint8, scale [B] f32).

    One scale per slot (the query is a single token; per-slot absmax keeps
    the full exponent range of the format in play).
    """
    fmt_obj = FORMATS[fmt]
    qf = q.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(qf), axis=(1, 2)), 1e-12)  # [B]
    scale = (amax / fmt_obj.max_normal).astype(jnp.float32)
    codes = encode(qf / scale[:, None, None], fmt_obj, mode)
    return codes, scale


# --------------------------------------------------------------------------- #
# Phase 1: per-page softmax partials — ONE implementation, called by both
# the Pallas kernel body and the pure-JAX reference.
# --------------------------------------------------------------------------- #
def _rowsum(x, pin: bool):
    """f32 sum over the last axis.  Pinned (traced by XLA CPU): a dot
    against ones behind a barrier — XLA CPU lowers dots the same in every
    graph context and reduce-sums not, which the bit-identity contracts
    need.  Unpinned (the compiled TPU kernel): a VPU reduction, exact in
    f32 where an MXU dot would round its operands."""
    if not pin:
        return x.sum(axis=-1)
    return jax.lax.optimization_barrier(jax.lax.dot_general(
        x, jnp.ones((x.shape[-1],), jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ))


def _page_scores_lns(q_codes, k_codes, qk_scale, fmt, mode, pin=True):
    """LNS integer-domain scores for one (slot, page).

    q_codes: [KV, G, hd] uint8; k_codes: [page, KV, hd] uint8;
    qk_scale: f32 scalar (q_scale * k_page_scale * hd**-0.5).
    Returns s [KV, G, page] f32.  Every q·k product is the paper's integer
    add + carry-in; the sum over hd runs on the wide f32 decode.  ``pin``
    places the XLA-CPU bit-identity barriers (see ``_page_partial``).
    """
    px = lns_prepare(q_codes, fmt, mode, side="x")        # fields [KV, G, hd]
    # transpose the codes, not the prepared fields: Mosaic cannot transpose
    # the boolean masks, and the integer prepare commutes with the transpose
    py = lns_prepare(jnp.transpose(k_codes, (1, 0, 2)), fmt, mode,
                     side="y")                            # fields [KV, page, hd]

    def ex(f):
        return None if f is None else f[:, :, None, :]    # [KV, G, 1, hd]

    def ey(f):
        return None if f is None else f[:, None, :, :]    # [KV, 1, page, hd]

    pxe = type(px)(*(ex(f) for f in px))
    pye = type(py)(*(ey(f) for f in py))                  # [KV, 1, page, hd]
    prod = lns_combine(pxe, pye, fmt)                     # [KV, G, page, hd] f32
    return _rowsum(prod, pin) * qk_scale


def _pin_add(a, b, pin: bool):
    """a + b, each term pinned first (the bit-identity barriers)."""
    if not pin:
        return a + b
    pin = jax.lax.optimization_barrier
    return pin(a) + pin(b)


def _page_partial(
    q_op, k_page, v_page, k_s, v_s, t0, length, *, fmt, mode, window, cap,
    pin=True, scale=None,
):
    """Softmax partials of one (slot, page): (m [KV,G], l [KV,G], o [KV,G,dv]).

    q_op: (codes [KV, G, hd], scale) for LNS pages, or float q [KV, G, hd]
    for float pages.  k_page/v_page: [page, KV, hd|dv] codes or float.
    ``scale`` is the softmax scale (None: ``hd**-0.5``).  A query wider
    than its k page is MLA's ``[q_pe ‖ q_eff]``: a row's score is the
    product of ``q_pe`` with its rope key (the k page) plus that of
    ``q_eff`` with its latent (the v page), each at its own page scale,
    and the values are the latents.
    t0: global position of the page's first row; length: valid tokens for
    this slot.  A fully masked page yields (m, l, o) = (NEG_INF, 0, 0):
    an exact identity of :func:`_fold`, which is why the kernel may skip
    it.  ``o`` is the p·V product before the 1/l normalization.

    ``pin`` wraps every order-sensitive f32 reduction in
    ``optimization_barrier``: XLA CPU traces this body both in plain jit
    (the ref) and in the Pallas interpreter, and the barriers keep the two
    bit-identical.  Mosaic has no lowering for the barrier, so the compiled
    TPU kernel traces with ``pin=False``: plain f32 sums and a p·V product
    at full f32 precision.
    """
    page = k_page.shape[0]
    dr = k_page.shape[-1]
    hd = (q_op[0] if fmt is not None else q_op).shape[-1]
    mla = hd > dr  # the query [q_pe ‖ q_eff] is wider than its rope key
    scale = hd**-0.5 if scale is None else scale
    if fmt is not None:
        q_codes, q_scale = q_op
        if mla:
            s = _pin_add(
                _page_scores_lns(q_codes[..., :dr], k_page,
                                 q_scale * k_s * scale, FORMATS[fmt],
                                 mode, pin),
                _page_scores_lns(q_codes[..., dr:], v_page,
                                 q_scale * v_s * scale, FORMATS[fmt],
                                 mode, pin), pin)
        else:
            s = _page_scores_lns(q_codes, k_page, q_scale * k_s * scale,
                                 FORMATS[fmt], mode, pin)
        vf = code_to_f32(v_page, FORMATS[fmt]) * v_s
    else:
        def dot(q, k):
            return jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32,
            )
        if mla:
            s = _pin_add(dot(q_op[..., :dr], k_page) * scale,
                         dot(q_op[..., dr:], v_page) * scale, pin)
        else:
            s = dot(q_op, k_page) * scale
        vf = v_page.astype(jnp.float32)
    if cap:
        s = jnp.tanh(s / cap) * cap

    t = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    ok = t < length
    if window:
        ok &= (length - 1 - t) < window
    s = jnp.where(ok, s, NEG_INF)

    m = s.max(axis=-1)
    # a masked row weighs exactly 0, also on a page with no valid row
    p = jnp.where(ok, jnp.exp(s - m[..., None]), 0.0)
    l = _rowsum(p, pin)
    # [KV, G, page] x [page, KV, dv] -> [KV, G, dv], batched over KV
    o = jax.lax.dot_general(
        p, vf, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32,
        precision=None if pin else jax.lax.Precision.HIGHEST,
    )
    return m, l, (jax.lax.optimization_barrier(o) if pin else o)


def _fold(acc, part, pin: bool):
    """Merge the softmax partial ``part`` into the running ``acc``, both
    (m [..., KV, G], l [..., KV, G], o [..., KV, G, dv]):
    ``m' = max(m, m_j)``, ``l' = l·e^(m−m') + l_j·e^(m_j−m')``, o alike.

    The dead partial (NEG_INF, 0, 0) is an exact identity on either side
    (``x·1 + 0·0``), so a fold that skips dead pages (the kernel) equals
    one that folds them (the references), bit for bit.  ``pin`` as in
    :func:`_page_partial`.
    """
    (m, l, o), (mj, lj, oj) = acc, part
    bar = jax.lax.optimization_barrier if pin else (lambda x: x)
    mx = jnp.maximum(m, mj)
    a = bar(jnp.exp(bar(m - mx)))
    c = bar(jnp.exp(bar(mj - mx)))
    return (mx, _pin_add(l * a, lj * c, pin),
            _pin_add(o * a[..., None], oj * c[..., None], pin))


def _dead_partial(shape, dv):
    """The (NEG_INF, 0, 0) partial: a fold's start, a page with no row."""
    return (jnp.full(shape, NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (dv,), jnp.float32))


def _fold_pages(m, l, o):
    """Per-page partials [B, maxp, KV, G(, dv)] folded, in page order,
    into one partial per slot: [B, KV, G(, dv)]."""
    B, _, KV, G = m.shape
    parts = tuple(jnp.moveaxis(x, 1, 0) for x in (m, l, o))
    acc, _ = jax.lax.scan(lambda acc, part: (_fold(acc, part, True), None),
                          _dead_partial((B, KV, G), o.shape[-1]), parts)
    return acc


# --------------------------------------------------------------------------- #
# Phase 2: normalize each slot's folded partial — shared verbatim by every
# impl.
# --------------------------------------------------------------------------- #
def _normalize(l, o):
    """l: [B, KV, G]; o: [B, KV, G, dv] -> [B, KV*G, dv]: ``o / l``.

    The entry barrier isolates the division from its (impl-specific)
    producers so XLA compiles it identically for every impl.  A slot with
    no row (l = 0) reads 0, not NaN.
    """
    l, o = jax.lax.optimization_barrier((l, o))
    out = o / jnp.maximum(l, 1e-37)[..., None]
    B, KV, G, dv = out.shape
    return out.reshape(B, KV * G, dv)


# --------------------------------------------------------------------------- #
# Pure-JAX reference (interpret-mode CI oracle; also the CPU serving path).
# --------------------------------------------------------------------------- #
def _heads_view(pages, KV):
    """[P, page, KV, d] view of a page array; MLA's page arrays are
    stored [P, page, d] (one latent or rope key per row)."""
    if pages.ndim == 3:
        return pages.reshape(pages.shape[:2] + (KV, pages.shape[2] // KV))
    return pages


def paged_attention_ref(
    q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt: Optional[str], mode: str, page_size: int, KV: int, G: int,
    window: int = 0, cap: float = 0.0, scale: Optional[float] = None,
):
    """Each slot's partial by a sequential fold — a ``lax.scan`` over its
    pages, of unbatched shapes, the same shapes one kernel program sees —
    then the shared normalization."""
    maxp = block_tables.shape[1]
    k_pages, v_pages = _heads_view(k_pages, KV), _heads_view(v_pages, KV)

    def slot(args):
        qb, bt, length = args
        if fmt is not None:
            codes, qs = qb
            q_slot = (codes.reshape(KV, G, -1), qs)
        else:
            q_slot = qb.reshape(KV, G, -1)

        def one_page(acc, j):
            pid = bt[j]
            part = _page_partial(
                q_slot, k_pages[pid], v_pages[pid], k_scale[pid],
                v_scale[pid], j * page_size, length,
                fmt=fmt, mode=mode, window=window, cap=cap,
                scale=scale,
            )
            return _fold(acc, part, True), None

        dead = _dead_partial((KV, G), v_pages.shape[-1])
        return jax.lax.scan(one_page, dead, jnp.arange(maxp))[0]

    _, l, o = jax.lax.map(slot, (q_op, block_tables, lengths))
    return _normalize(l, o)


# --------------------------------------------------------------------------- #
# Natively-batched phase 1 (impl="batch"): gather every slot's pages up
# front and run the LNS machinery on the full [B, maxp, ...] arrays.  No
# vmap (``optimization_barrier`` has no batching rule) — the broadcasts are
# written out by hand, element-for-element the same ops as ``_page_partial``
# so the result is bit-identical to the sequential reference.  This replaces
# two nested ``lax.map`` while-loops per layer on the CPU serving path.
# --------------------------------------------------------------------------- #
def _insert_rows(gathered, row, logical, rows, mask):
    """Insert one freshly-written row per slot into the gathered page block.

    gathered: [B, maxp, page, KV, hd]; row: [B, KV, hd]; logical/rows: [B]
    int32 (logical page index and in-page row of each slot's write); mask:
    [B] bool or None.  Equals scatter-into-pages-then-gather for every lane
    whose target page is exclusively owned (the write contract).
    """
    B, maxp, page = gathered.shape[:3]
    sel = (jnp.arange(maxp, dtype=jnp.int32)[None, :, None] ==
           logical[:, None, None])
    sel &= (jnp.arange(page, dtype=jnp.int32)[None, None, :] ==
            rows[:, None, None])
    if mask is not None:
        sel &= mask[:, None, None]
    return jnp.where(sel[..., None, None], row[:, None, None], gathered)


def _batch_partials(
    q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt, mode, KV, G, window, cap, inserts=None, scale=None,
):
    """All (slot, page) softmax partials at once, folded in page order
    into one per slot: (m, l, o) shaped [B, KV, G(, dv)] — the same
    partials the ref builds."""
    B, maxp = block_tables.shape
    k_pages, v_pages = _heads_view(k_pages, KV), _heads_view(v_pages, KV)
    kg = k_pages[block_tables]            # [B, maxp, page, KV, hd]
    vg = v_pages[block_tables]            # [B, maxp, page, KV, dv]
    ksg = k_scale[block_tables]           # [B, maxp]
    vsg = v_scale[block_tables]
    page = kg.shape[2]
    if inserts is not None:
        k_row, v_row, logical, rows, imask = inserts
        kg = _insert_rows(kg, k_row, logical, rows, imask)
        vg = _insert_rows(vg, v_row, logical, rows, imask)
    dr = kg.shape[-1]
    hd = (q_op[0] if fmt is not None else q_op).shape[-1]
    scale = hd**-0.5 if scale is None else scale
    if fmt is not None:
        fmt_obj = FORMATS[fmt] if isinstance(fmt, str) else fmt
        codes, qs = q_op
        qc = codes.reshape(B, KV, G, hd)

        def scores(qc, kg, ksg, c):
            px = lns_prepare(qc, fmt_obj, mode, side="x")   # [B,KV,G,d]
            py = lns_prepare(kg, fmt_obj, mode, side="y")   # [B,maxp,page,KV,d]

            def ex(f):
                return None if f is None else f[:, None, :, :, None, :]

            def ey(f):
                if f is None:
                    return None
                return jnp.transpose(f, (0, 1, 3, 2, 4))[:, :, :, None, :, :]

            prod = lns_combine(type(px)(*(ex(f) for f in px)),
                               type(py)(*(ey(f) for f in py)), fmt_obj)
            # [B, maxp, KV, G, page, d] -> sum over d, pinned like the ref
            ssum = jax.lax.dot_general(
                prod, jnp.ones((prod.shape[-1],), jnp.float32),
                (((5,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            qk = qs[:, None] * ksg * c                      # [B, maxp]
            return (jax.lax.optimization_barrier(ssum)
                    * qk[:, :, None, None, None])

        if hd > dr:  # MLA, as in _page_partial
            s = _pin_add(scores(qc[..., :dr], kg, ksg, scale),
                         scores(qc[..., dr:], vg, vsg, scale), True)
        else:
            s = scores(qc, kg, ksg, scale)
        vf = code_to_f32(vg, fmt_obj) * vsg[:, :, None, None, None]
    else:
        def dot(q, kg):
            qb = jnp.broadcast_to(
                q.astype(jnp.float32).reshape(B, 1, KV, G, q.shape[-1]),
                (B, maxp, KV, G, q.shape[-1]),
            )
            kt = jnp.transpose(kg.astype(jnp.float32), (0, 1, 3, 2, 4))
            return jax.lax.dot_general(
                qb, kt, (((4,), (4,)), ((0, 1, 2), (0, 1, 2))),
                preferred_element_type=jnp.float32,
            )

        if hd > dr:
            s = _pin_add(dot(q_op[..., :dr], kg) * scale,
                         dot(q_op[..., dr:], vg) * scale, True)
        else:
            s = dot(q_op, kg) * scale
        vf = vg.astype(jnp.float32)
    if cap:
        s = jnp.tanh(s / cap) * cap

    t = (jnp.arange(maxp, dtype=jnp.int32) * page)[None, :, None, None, None]
    t = t + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, page), 4)
    ln = lengths[:, None, None, None, None]
    ok = t < ln
    if window:
        ok &= (ln - 1 - t) < window
    pin = jax.lax.optimization_barrier
    s = pin(jnp.where(ok, s, NEG_INF))

    m = pin(s.max(axis=-1))                              # [B, maxp, KV, G]
    p = pin(jnp.where(ok, pin(jnp.exp(pin(s - m[..., None]))), 0.0))
    # Page-row sum as a dot against ones: XLA CPU lowers dots consistently
    # across graph contexts, while reduce-sum vectorization is context
    # dependent (1-ulp drift when e.g. cache scatters share the graph).
    l = pin(jax.lax.dot_general(
        p, jnp.ones((page,), jnp.float32), (((4,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ))
    vt = jnp.transpose(vf, (0, 1, 3, 2, 4))              # [B,maxp,KV,page,dv]
    o = pin(jax.lax.dot_general(
        p, vt, (((4,), (3,)), ((0, 1, 2), (0, 1, 2))),
        preferred_element_type=jnp.float32,
    ))
    return _fold_pages(m, l, o)


def paged_attention_batch(
    q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt: Optional[str], mode: str, page_size: int, KV: int, G: int,
    window: int = 0, cap: float = 0.0, inserts=None, scale: Optional[float] = None,
):
    _, l, o = _batch_partials(
        q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
        fmt=fmt, mode=mode, KV=KV, G=G, window=window, cap=cap,
        inserts=inserts, scale=scale,
    )
    return _normalize(l, o)


def paged_attention_partials(
    q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt: Optional[str], mode: str, page_size: int, KV: int, G: int,
    window: int = 0, cap: float = 0.0,
):
    """One shard's locally-combined softmax partials (flash-decoding
    KV-split serving): (m [B, KV, G], l [B, KV, G], o [B, KV, G, dv]),
    with ``o`` still un-normalized.  Pages this shard does not hold are
    masked by pointing their block-table entries at the null page with
    ``lengths`` clipped, or simply by passing a block table whose rows
    list only local pages — fully masked pages are the fold's identity
    and drop out.  Feed the result to
    :func:`combine_partials_psum` inside ``shard_map``.
    """
    return _batch_partials(
        q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
        fmt=fmt, mode=mode, KV=KV, G=G, window=window, cap=cap,
    )


def combine_partials_psum(m, l, o, axis_name: str):
    """Cross-shard log-sum-exp combine: one pmax + two psums.

    Inside ``shard_map``, each shard holds its pages' locally-combined
    partials (from :func:`paged_attention_partials`); this merges them
    into the normalized attention output [B, KV*G, dv].

    Collective placement: this is the flash-decoding KV-split path the
    two-pass softmax was designed for — allclose-exact, but NOT
    bit-identical across shard counts (the floating-point merge order of
    page partials changes with the split).  The serving engine's
    bit-identical TP therefore shards *heads* (cross-shard combine = pure
    concatenation) and reserves this helper for throughput-oriented
    page-sharded deployments where allclose is the contract.
    """
    M = jax.lax.pmax(m, axis_name)
    w = jnp.exp(m - M)
    l_tot = jax.lax.psum(w * l, axis_name)
    o_tot = jax.lax.psum(w[..., None] * o, axis_name)
    out = o_tot / jnp.maximum(l_tot, 1e-37)[..., None]
    B, KV, G, dv = out.shape
    return out.reshape(B, KV * G, dv)


# --------------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------------- #
def walked_pages(lengths, page, maxp, xp=jnp):
    """Pages the kernel walks for a slot of attend length ``lengths``: the
    pages that hold a row, ``ceil(n/page)``, at least page 0 (a slot with
    no row walks it fully masked) and at most ``maxp``.  ``xp=np``
    reckons it on the host, with no device op."""
    return xp.clip((lengths + page - 1) // page, 1, maxp)


def _paged_kernel(
    *refs, fmt, mode, page, KV, G, window, cap, spb, ppb, maxp, fused,
    pin, scale,
):
    """Grid (Bp/spb,) program: walks the live pages of ``spb`` slots,
    folding each page's partial into its slot's one running partial.

    A slot holds rows in the pages :func:`walked_pages` counts; the
    program visits those in blocks of ``ppb`` pages and no others.  The
    K/V pools stay in HBM: each live page is gathered through the
    scalar-prefetched block table by its own DMA into a double-buffered
    VMEM block, and the next block's copies (the next slot's first block
    after a slot's last) are started before this block's compute.  The
    output block holds one ``(m, l, o)`` per slot, started at
    ``(NEG_INF, 0, 0)`` and folded (:func:`_fold`) in walk order; a page
    the walk skips would fold in as that identity.

    Scalars ride in as scalar-prefetch (SMEM) operands: block tables,
    lengths, per-slot q scales and the per-(slot, page) K/V scales gathered
    through the block table, so SMEM holds [Bp, maxp] values however large
    the page pool is.  With ``fused`` the new token's row codes ride in as
    extra operands and are spliced into the gathered page block in-register
    before the partial — the kernel never reads the scattered cache arrays.
    """
    npre = 8 if fused else 5
    bt_ref, len_ref, qs_ref, ks_ref, vs_ref = refs[:5]
    if fused:
        log_ref, row_ref, msk_ref = refs[5:8]
        kins_ref, vins_ref = refs[npre:npre + 2]
        refs = refs[npre + 2:]
    else:
        refs = refs[npre:]
    q_ref, k_hbm, v_hbm, m_ref, l_ref, o_ref, k_buf, v_buf, sem = refs
    b = pl.program_id(0)

    m_ref[...], l_ref[...], o_ref[...] = _dead_partial(m_ref.shape,
                                                       o_ref.shape[-1])

    def widen(codes):
        # 8-bit codes widen to int32 on load (the LNS prepare does the same
        # cast): Mosaic lowers the masks and selects on 32-bit layouts only
        return codes.astype(jnp.int32) if fmt is not None else codes

    def live_pages(i):
        """Pages of slot ``i`` walked."""
        return walked_pages(len_ref[b * spb + i], page, maxp)

    def n_blocks(i):
        return (live_pages(i) + ppb - 1) // ppb

    def each_live_page(i, blk, fn):
        """``fn(jj, jp)`` for the pages of block ``blk`` that hold a row
        (a loop, so the body is compiled once for every block shape)."""
        n = jnp.minimum(ppb, live_pages(i) - blk * ppb)

        def body(jj, carry):
            fn(jj, blk * ppb + jj)
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def copies(i, jp, buf, jj):
        pid = bt_ref[b * spb + i, jp]
        return (pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, jj],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, jj],
                                      sem.at[1, buf]))

    def fetch(i, blk, buf):
        def start(jj, jp):
            for c in copies(i, jp, buf, jj):
                c.start()
        each_live_page(i, blk, start)

    def compute(i, blk, buf):
        bs = b * spb + i
        q = widen(q_ref[i])
        q_op = (q, qs_ref[bs]) if fmt is not None else q

        def one(jj, jp):
            for c in copies(i, jp, buf, jj):
                c.wait()
            kp_blk = _unflatten_page(widen(k_buf[buf, jj]), page, KV)
            vp_blk = _unflatten_page(widen(v_buf[buf, jj]), page, KV)
            if fused:
                hit = (log_ref[bs] == jp) & (msk_ref[bs] != 0)
                row = jax.lax.broadcasted_iota(
                    jnp.int32, (page, 1, 1), 0) == row_ref[bs]
                kp_blk = jnp.where(hit & row, widen(kins_ref[i][None]), kp_blk)
                vp_blk = jnp.where(hit & row, widen(vins_ref[i][None]), vp_blk)
            part = _page_partial(
                q_op, kp_blk, vp_blk, ks_ref[bs, jp], vs_ref[bs, jp],
                jp * page, len_ref[bs],
                fmt=fmt, mode=mode, window=window, cap=cap, pin=pin,
                scale=scale,
            )
            acc = (m_ref[i], l_ref[i], o_ref[i])
            m_ref[i], l_ref[i], o_ref[i] = _fold(acc, part, pin)
        each_live_page(i, blk, one)

    def slot(i, carry):
        done, ready = carry     # ready: slot i's first block is in flight
        nblk = n_blocks(i)
        pl.when((nblk > 0) & (ready == 0))(lambda: fetch(i, 0, done % 2))
        nxt = jnp.minimum(i + 1, spb - 1)
        ahead = (i + 1 < spb) & (n_blocks(nxt) > 0)

        def block(blk, done):
            buf = done % 2      # blocks walked so far pick the buffer
            pl.when(blk + 1 < nblk)(lambda: fetch(i, blk + 1, 1 - buf))
            pl.when((blk + 1 == nblk) & ahead)(lambda: fetch(nxt, 0, 1 - buf))
            compute(i, blk, buf)
            return done + 1
        done = jax.lax.fori_loop(0, nblk, block, done)
        return done, (ahead & (nblk > 0)).astype(jnp.int32)

    jax.lax.fori_loop(0, spb, slot, (jnp.int32(0), jnp.int32(0)))


def _page_rows(page, KV, hd):
    """(rows, lanes) of the 2-D view a page is gathered in.

    A DMA moves whole (sublane, 128-lane) tiles, so a page is gathered as
    rows of at least 128 lanes: ``[page, KV*hd]`` when ``KV*hd`` is a
    multiple of 128; ``[page*KV*hd/128, 128]`` when a whole number of page
    rows fills each 128-lane row; else ``[page, KV*hd]``, which the
    compiler may refuse.
    """
    width = KV * hd
    if width % 128 and 128 % width == 0 and page * width % 128 == 0:
        return page * width // 128, 128
    return page, width


def _unflatten_page(x, page, KV):
    """[rows, lanes] gathered page (row-major bytes of the page) ->
    [page, KV, hd], by lane slices and stacks only: Mosaic has no general
    reshape that splits the lane axis."""
    rows, lanes = x.shape
    if rows != page:      # several page rows share one 128-lane row
        n = page // rows
        w = lanes // n
        x = jnp.stack([x[:, i * w:(i + 1) * w] for i in range(n)],
                      axis=1).reshape(page, w)
    hd = x.shape[1] // KV
    if KV == 1:
        return x[:, None, :]
    return jnp.stack([x[:, h * hd:(h + 1) * hd] for h in range(KV)], axis=1)


def _pad_rows(x, n):
    return x if n == 0 else jnp.pad(x, ((0, n),) + ((0, 0),) * (x.ndim - 1))


def _paged_kernel_call(q_in, q_scale, k_pages, v_pages, k_scale, v_scale,
                       block_tables, lengths, **kw):
    """The paged kernel's attention: its partials, then phase 2."""
    _, l, o = _paged_kernel_partials(
        q_in, q_scale, k_pages, v_pages, k_scale, v_scale, block_tables,
        lengths, **kw)
    return _normalize(l, o)


def _paged_kernel_partials(
    q_in, q_scale, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
    *, fmt, mode, page_size, KV, G, window, cap, interpret,
    ppb: int = 1, spb: int = 1, inserts=None, scale: Optional[float] = None,
):
    """Launch the paged kernel: one folded partial (m, l, o) per slot,
    shaped [B, KV, G(, dv)], as :func:`_batch_partials`.

    ``spb`` slots per program, walked ``ppb`` pages per block.  The slot
    axis is padded up to ``spb`` — padded lanes carry length 0 and page 0
    of their block table, walk that one fully masked page, and are sliced
    off — so the result is bit-identical for every (ppb, spb).

    Under a serving mesh that shards KV heads (``parallel.hints``) the
    launch runs under ``shard_map`` on each shard's KV heads: GSPMD cannot
    partition a Mosaic kernel, and attention is independent per KV head,
    so the shards' partials concatenate.
    """
    from ..parallel.hints import kv_head_mesh

    B, H, hd = q_in.shape
    Bp = -(-B // spb) * spb
    pad_b = Bp - B
    bt = _pad_rows(block_tables, pad_b)
    prefetch = [bt, _pad_rows(lengths, pad_b), _pad_rows(q_scale, pad_b),
                k_scale[bt], v_scale[bt]]
    args = [_pad_rows(q_in, pad_b).reshape(Bp, KV, G, hd), k_pages, v_pages]
    fused = inserts is not None
    if fused:
        k_row, v_row, logical, rows, imask = inserts
        imask = (jnp.ones((B,), jnp.int32) if imask is None
                 else imask.astype(jnp.int32))
        prefetch += [_pad_rows(logical, pad_b), _pad_rows(rows, pad_b),
                     _pad_rows(imask, pad_b)]
        args += [_pad_rows(k_row, pad_b), _pad_rows(v_row, pad_b)]
    launch = functools.partial(
        _paged_pallas, n_prefetch=len(prefetch), fmt=fmt, mode=mode,
        page_size=page_size, window=window, cap=cap, interpret=interpret,
        ppb=ppb, spb=spb, scale=scale,
    )
    sharded = kv_head_mesh()
    if sharded is not None:
        mesh, ax = sharded
        P = jax.sharding.PartitionSpec
        launch = jax.shard_map(
            launch, mesh=mesh,
            in_specs=(P(),) * len(prefetch) + (
                P(None, ax), P(None, None, ax), P(None, None, ax))
            + (P(None, ax),) * (len(args) - 3),
            out_specs=(P(None, ax),) * 3, check_vma=False,
        )
    m, l, o = launch(*prefetch, *args)
    return m[:B], l[:B], o[:B]


def _paged_pallas(*args, n_prefetch, fmt, mode, page_size, window, cap,
                  interpret, ppb, spb, scale):
    """The pallas_call of :func:`_paged_kernel_partials` on (possibly
    shard-local) operands: scalar prefetch, q [Bp, KV, G, hd], the page
    arrays (left in HBM), then the fused row inserts [Bp, KV, hd] x2 when
    present."""
    prefetch = args[:n_prefetch]
    q, k_pages, v_pages, *rows = args[n_prefetch:]
    Bp, KV, G, hd = q.shape
    dv = v_pages.shape[-1]
    maxp = prefetch[0].shape[1]
    fused = bool(rows)
    kernel = functools.partial(
        _paged_kernel, fmt=fmt, mode=mode, page=page_size, KV=KV, G=G,
        window=window, cap=cap, spb=spb, ppb=ppb, maxp=maxp,
        fused=fused, pin=bool(interpret), scale=scale,
    )

    def slot_spec(x):
        return pl.BlockSpec((spb,) + x.shape[1:],
                            lambda b, *pref: (b,) + (0,) * (x.ndim - 1))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    k_rows = _page_rows(page_size, KV, k_pages.shape[-1])
    v_rows = _page_rows(page_size, KV, dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(Bp // spb,),
        in_specs=[slot_spec(r) for r in rows] + [slot_spec(q), hbm, hbm],
        out_specs=[
            pl.BlockSpec((spb, KV, G), lambda b, *pref: (b, 0, 0)),
            pl.BlockSpec((spb, KV, G), lambda b, *pref: (b, 0, 0)),
            pl.BlockSpec((spb, KV, G, dv), lambda b, *pref: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, ppb) + k_rows, k_pages.dtype),
            pltpu.VMEM((2, ppb) + v_rows, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bp, KV, G), jnp.float32),
            jax.ShapeDtypeStruct((Bp, KV, G), jnp.float32),
            jax.ShapeDtypeStruct((Bp, KV, G, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(*prefetch, *rows, q, k_pages.reshape((-1,) + k_rows),
      v_pages.reshape((-1,) + v_rows))


# --------------------------------------------------------------------------- #
# Public entry point
# --------------------------------------------------------------------------- #
def _resolve_impl(impl: str, interpret):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if impl == "auto":
        impl = "batch" if jax.default_backend() == "cpu" else "kernel"
    return impl, interpret


def _kernel_blocks(impl, block_tables, page_size, KV, G, hd, fmt, interpret,
                   site=""):
    """Autotuned (pages_per_block, slots_per_block) for the kernel grid."""
    if impl != "kernel":
        return 1, 1
    from .autotune import paged_blocks

    B, maxp = block_tables.shape
    return paged_blocks(B, maxp, page_size, KV, G, hd,
                        fmt=fmt or "f32", interpret=interpret, site=site)


def paged_decode_attention(
    q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt: Optional[str], n_kv_heads: int, mode: str = "rne",
    window: int = 0, cap: float = 0.0, scale: Optional[float] = None,
    impl: str = "auto", interpret: Optional[bool] = None, site: str = "",
):
    """Decode attention against a paged KV cache.

    q: [B, 1, H, hd] float; k_pages/v_pages: [P, page, KV, hd|dv] — uint8
    FP8 codes when ``fmt`` names a format, float otherwise; k_scale/v_scale:
    [P] f32 per-page scales (ignored for float pages); block_tables:
    [B, maxp] int32 page ids (unowned entries must point at a reserved page
    — they are masked by ``lengths``); lengths: [B] int32 valid tokens.

    ``impl``: "kernel" (Pallas), "ref" (sequential pure JAX oracle),
    "batch" (vectorized pure JAX — the CPU serving path), "auto" = batch on
    CPU, kernel on accelerators.  All three are bit-identical.  ``site``
    keys the autotune cache entry for the kernel block shape.

    ``scale``: the softmax scale, by default ``hd**-0.5``.  MLA (a query
    wider than its k pages): absorbed latent attention, one KV head.  q is ``[q_pe ‖ q_eff]`` [B, 1, H, dr + L];
    k_pages holds the rope keys [P, page, dr] and v_pages the latents
    [P, page, L], which are the values too.
    Returns [B, 1, H, dv] in q.dtype.
    """
    impl, interpret = _resolve_impl(impl, interpret)
    ppb, spb = _kernel_blocks(impl, block_tables, k_pages.shape[1],
                              n_kv_heads, q.shape[2] // n_kv_heads,
                              q.shape[3], fmt, interpret, site=site)
    return _paged_decode_attention(
        q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
        fmt=fmt, n_kv_heads=n_kv_heads, mode=mode, window=window, cap=cap,
        impl=impl, interpret=interpret, ppb=ppb, spb=spb,
        scale=scale,
    )


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "n_kv_heads", "mode", "window", "cap", "impl",
                     "interpret", "ppb", "spb", "scale"),
)
def _paged_decode_attention(
    q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths, *,
    fmt: Optional[str], n_kv_heads: int, mode: str,
    window: int, cap: float, impl: str, interpret: bool,
    ppb: int = 1, spb: int = 1, scale: Optional[float] = None,
):
    B, one, H, hd = q.shape
    assert one == 1, "paged decode attention is single-position"
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    KV = n_kv_heads
    G = H // KV
    q_in = q.reshape(B, H, hd)
    if fmt is not None:
        codes, qs = quantize_q(q_in, fmt)
        q_op = (codes, qs)
    else:
        q_op = q_in.astype(jnp.float32)

    if impl == "ref":
        out = paged_attention_ref(
            q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
            fmt=fmt, mode=mode, page_size=k_pages.shape[1], KV=KV, G=G,
            window=window, cap=cap, scale=scale,
        )
    elif impl == "batch":
        out = paged_attention_batch(
            q_op, k_pages, v_pages, k_scale, v_scale, block_tables, lengths,
            fmt=fmt, mode=mode, page_size=k_pages.shape[1], KV=KV, G=G,
            window=window, cap=cap, scale=scale,
        )
    elif impl == "kernel":
        if fmt is not None:
            q_arr, q_scale = q_op
        else:
            q_arr, q_scale = q_op, jnp.ones((B,), jnp.float32)
        out = _paged_kernel_call(
            q_arr, q_scale, k_pages, v_pages, k_scale, v_scale,
            block_tables, lengths, fmt=fmt, mode=mode,
            page_size=k_pages.shape[1], KV=KV, G=G, window=window, cap=cap,
            interpret=interpret, ppb=ppb, spb=spb, scale=scale,
        )
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return out.reshape(B, 1, H, -1).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Fused KV-write + attend: the decode hot path's single launch.
# --------------------------------------------------------------------------- #
def fused_decode_write_attend(
    q, k_new, v_new, k_pages, v_pages, k_scale, v_scale, block_tables,
    lengths, *, fmt: Optional[str], n_kv_heads: int, mode: str = "rne",
    kv_mode: str = "stochastic", k_key=None, v_key=None, write_mask=None,
    window: int = 0, cap: float = 0.0, scale: Optional[float] = None,
    impl: str = "auto", interpret: Optional[bool] = None, site: str = "",
):
    """Write one decode token's K/V into its page AND attend, in one launch.

    q: [B, 1, H, hd]; k_new/v_new: [B, KV, hd] float (this token's
    projected K/V); ``lengths`` are **pre-write** context lengths — the
    write lands at position ``lengths`` and attention covers
    ``lengths + 1`` tokens, exactly like the unfused
    ``write_token_page`` -> ``paged_decode_attention`` composition.

    The row codes and page scales are computed once (identical math to
    ``write_token_page``, including the stochastic-rounding streams fed by
    ``k_key``/``v_key`` and the explicit ``write_mask`` null-page
    convention).  The cache scatter and the attention both consume them,
    but the attention inserts the row into the *gathered* page block
    in-flight instead of reading the scattered arrays — so the launch's
    critical path never waits for the O(P) cache update.

    Bit-identity contract: identical to the unfused composition on every
    lane whose ``write_mask`` is set (masked lanes share the null page,
    whose contents depend on host scatter order — both compositions mask
    those outputs downstream).

    ``scale`` and MLA as :func:`paged_decode_attention`: k_new is
    the token's rope key [B, 1, dr] and v_new its latent [B, 1, L].

    Returns ``(out [B, 1, H, dv], new_k_pages, new_k_scale, new_v_pages,
    new_v_scale)``.
    """
    impl, interpret = _resolve_impl(impl, interpret)
    ppb, spb = _kernel_blocks(impl, block_tables, k_pages.shape[1],
                              n_kv_heads, q.shape[2] // n_kv_heads,
                              q.shape[3], fmt, interpret, site=site)
    out, new_kp, new_ks, new_vp, new_vs, _aux = _fused_decode_write_attend(
        q, k_new, v_new, k_pages, v_pages, k_scale, v_scale, block_tables,
        lengths, k_key, v_key, write_mask,
        fmt=fmt, n_kv_heads=n_kv_heads, mode=mode, kv_mode=kv_mode,
        window=window, cap=cap, impl=impl, interpret=interpret,
        ppb=ppb, spb=spb, scale=scale,
    )
    return out, new_kp, new_ks, new_vp, new_vs


def _set_rows(pages, pids, rows, row):
    """Write one [KV, hd] row per lane through the [P, page, KV*hd] view
    the kernel gathers from, so the compiler keeps one layout of the pool
    for the write and the attend."""
    P, page = pages.shape[:2]
    width = row.shape[-2] * row.shape[-1]
    flat = pages.reshape(P, page, width)
    return flat.at[pids, rows].set(row.reshape(-1, width)).reshape(pages.shape)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "n_kv_heads", "mode", "kv_mode", "window", "cap",
                     "impl", "interpret", "ppb", "spb", "scale"),
)
def _fused_decode_write_attend(
    q, k_new, v_new, k_pages, v_pages, k_scale, v_scale, block_tables,
    lengths, k_key, v_key, write_mask, *, fmt, n_kv_heads, mode, kv_mode,
    window, cap, impl, interpret, ppb, spb, scale=None,
):
    from ..serving.page_pool import token_row_codes

    B, one, H, hd = q.shape
    assert one == 1, "fused decode write+attend is single-position"
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    page_size = k_pages.shape[1]
    KV = n_kv_heads
    G = H // KV
    logical = lengths // page_size
    rows = lengths - logical * page_size
    page_ids = jnp.take_along_axis(block_tables, logical[:, None], axis=1)[:, 0]

    pids_k, k_row, ks_new = token_row_codes(
        k_scale, k_new, page_ids, rows, fmt=fmt, mode=kv_mode, key=k_key,
        write_mask=write_mask,
    )
    pids_v, v_row, vs_new = token_row_codes(
        v_scale, v_new, page_ids, rows, fmt=fmt, mode=kv_mode, key=v_key,
        write_mask=write_mask,
    )
    # cache carry for the next step — off the attention's critical path
    new_kp = _set_rows(k_pages, pids_k, rows, k_row)
    new_vp = _set_rows(v_pages, pids_v, rows, v_row)
    if fmt is not None:
        new_ks = k_scale.at[pids_k].set(ks_new)
        new_vs = v_scale.at[pids_v].set(vs_new)
    else:
        new_ks, new_vs = k_scale, v_scale

    q_in = q.reshape(B, H, hd)
    if fmt is not None:
        codes, qs = quantize_q(q_in, fmt)
        q_op = (codes, qs)
    else:
        q_op = q_in.astype(jnp.float32)
    attend_len = lengths + 1
    mask = None if write_mask is None else jnp.asarray(write_mask, bool)

    aux = ()
    if impl == "ref":
        # oracle: literal write-then-attend over the scattered arrays
        out = paged_attention_ref(
            q_op, new_kp, new_vp, new_ks, new_vs, block_tables, attend_len,
            fmt=fmt, mode=mode, page_size=page_size, KV=KV, G=G,
            window=window, cap=cap, scale=scale,
        )
    elif impl == "batch":
        m, l, o = _batch_partials(
            q_op, k_pages, v_pages, new_ks, new_vs, block_tables, attend_len,
            fmt=fmt, mode=mode, KV=KV, G=G, window=window, cap=cap,
            inserts=(k_row, v_row, logical, rows, mask), scale=scale,
        )
        out = _normalize(l, o)
        # Materialize the softmax partials as (discarded) graph outputs.
        # Barriers alone do not stop XLA CPU from duplicating their
        # producers into downstream fusions with context-dependent
        # vectorization; an output forces one canonical computation, which
        # keeps the fused path bit-identical to write-then-attend.
        aux = (m, l)
    elif impl == "kernel":
        if fmt is not None:
            q_arr, q_scale = q_op
        else:
            q_arr, q_scale = q_op, jnp.ones((B,), jnp.float32)
        out = _paged_kernel_call(
            q_arr, q_scale, k_pages, v_pages, new_ks, new_vs,
            block_tables, attend_len, fmt=fmt, mode=mode,
            page_size=page_size, KV=KV, G=G, window=window, cap=cap,
            interpret=interpret, ppb=ppb, spb=spb,
            inserts=(k_row, v_row, logical, rows, mask), scale=scale,
        )
    else:
        raise ValueError(f"unknown impl {impl!r}")
    out = out.reshape(B, 1, H, -1).astype(q.dtype)
    return out, new_kp, new_ks, new_vp, new_vs, aux
