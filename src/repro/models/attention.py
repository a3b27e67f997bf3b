"""Attention modules: GQA (with qk-norm, softcap, sliding window) and MLA.

Each module provides ``init(rng, cfg)``, ``forward(...)`` for full-sequence
(train/prefill) and ``decode(...)`` for single-token cache attention.

Caches:
  * GQA:  {"k": [B, S, KV, hd], "v": [B, S, KV, dv]}
  * MLA:  {"ckv": [B, S, lora], "kpe": [B, S, rope]}  (compressed — the point
    of MLA; decode uses the absorbed-matrices formulation)

Paged (serving) caches hold page arrays of the global pool, {"kp", "vp",
"ks", "vs"}: GQA's K and V pages [P, page, KV, hd], or MLA's rope keys
[P, page, rope] and latents [P, page, lora], each with one scale per page.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import numerics
from ..parallel.hints import hint
from .layers import (
    chunked_attention,
    decode_attention,
    qk_rms_norm,
    qlinear,
    rms_norm,
    rope,
    softcap,
    yarn_mscale,
)


def _kv_store(x, cfg):
    """To cache representation (FP8 codes when the policy quantizes KV)."""
    return numerics.kv_encode(x, cfg.policy)


def _kv_load(x, cfg):
    return numerics.kv_decode(x, cfg.policy)


def _kv_fp8(cfg) -> bool:
    return numerics.kv_quantized(cfg.policy)


def _init(rng, shape, dtype, scale=0.02):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# GQA
# --------------------------------------------------------------------------- #
def gqa_init(rng, cfg):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.pdtype
    ks = jax.random.split(rng, 4)
    p = {
        "wq": _init(ks[0], (D, H * hd), dt),
        "wk": _init(ks[1], (D, KV * hd), dt),
        "wv": _init(ks[2], (D, KV * hd), dt),
        "wo": _init(ks[3], (H * hd, D), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _gqa_qkv(p, x, cfg, positions, use_rope=True, site="blocks.*.attn"):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pol = cfg.policy
    q = qlinear(x, p["wq"], pol, p.get("bq"), site=f"{site}.wq").reshape(B, S, H, hd)
    k = qlinear(x, p["wk"], pol, p.get("bk"), site=f"{site}.wk").reshape(B, S, KV, hd)
    v = qlinear(x, p["wv"], pol, p.get("bv"), site=f"{site}.wv").reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = qk_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = qk_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, x, cfg, *, is_global: bool, positions, cross_kv=None,
                causal=True, use_rope=True, q_chunk=512, kv_chunk=1024,
                site="blocks.*.attn"):
    """Full-sequence attention. Returns (out, cache_entries)."""
    q, k, v = _gqa_qkv(p, x, cfg, positions, use_rope, site=site)
    # Serving-TP roles (no-ops outside the engine's hint context); see
    # gqa_decode_paged for the concatenation-only sharding contract.
    q = hint(q, "tp_heads")
    k = hint(k, "tp_kv")
    v = hint(v, "tp_kv")
    window = 0 if is_global else cfg.window
    if cross_kv is not None:  # enc-dec cross attention uses given k/v
        k, v = cross_kv
        out = chunked_attention(q, k, v, causal=False, cap=cfg.attn_softcap,
                                q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_softcap,
                                q_chunk=q_chunk, kv_chunk=kv_chunk)
    B, S, _, _ = q.shape
    out = hint(out, "tp_gather")  # all-gather heads before the wo matmul
    y = qlinear(out.reshape(B, S, -1), p["wo"], cfg.policy, site=f"{site}.wo")
    return y, {"k": _kv_store(k, cfg), "v": _kv_store(v, cfg)}


def gqa_decode(p, x, cfg, *, is_global: bool, cache, pos, cross_kv=None,
               use_rope=True, site="blocks.*.attn"):
    """x: [B, 1, D]; cache k/v: [B, S, KV, hd]; pos: position index — a
    scalar, or a [B] vector of per-slot positions (serving batches)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k_new, v_new = _gqa_qkv(p, x, cfg, positions, use_rope, site=site)
    if cross_kv is not None:
        k, v = cross_kv
        out = decode_attention(q, k, v, pos=k.shape[1] - 1, cap=cfg.attn_softcap)
        new_cache = cache
    else:
        k_c = _kv_store(k_new, cfg) if _kv_fp8(cfg) else k_new.astype(cache["k"].dtype)
        v_c = _kv_store(v_new, cfg) if _kv_fp8(cfg) else v_new.astype(cache["v"].dtype)
        W = cache["k"].shape[1]
        window = 0 if is_global else cfg.window
        ring = bool(window) and W <= window  # ring buffer cache
        write = jax.lax.rem(pos, W) if ring else pos
        b_ix = jnp.arange(B)
        k = cache["k"].at[b_ix, write].set(k_c[:, 0])
        v = cache["v"].at[b_ix, write].set(v_c[:, 0])
        out = decode_attention(q, _kv_load(k, cfg), _kv_load(v, cfg),
                               pos=pos, window=0 if ring else window,
                               cap=cfg.attn_softcap, ring=ring)
        new_cache = {"k": k, "v": v}
    y = qlinear(out.reshape(B, 1, -1), p["wo"], cfg.policy, site=f"{site}.wo")
    return y, new_cache


def gqa_decode_paged(p, x, cfg, *, is_global: bool, cache, paged,
                     use_rope=True, site="blocks.*.attn"):
    """GQA decode against the global page pool (serving path).

    x: [B, 1, D]; cache: this layer's page arrays {"kp", "vp", "ks", "vs"}
    (kp/vp: [P, page, KV, hd], ks/vs: [P] f32); paged: the step's shared
    state {"block_tables" [B, maxp], "lengths" [B] (context length per slot
    BEFORE this token), "page_size", "key" (stochastic-write PRNG key or
    None), "active" (optional [B] bool write mask)}.  Writes the new
    token's K/V into its page (fresh pages get a pow2 scale from the
    token's absmax), then runs the integer-domain paged decode attention.
    Returns (y, new_cache).

    Two serving contracts live here:

      * **Explicit write mask.**  ``paged["active"]`` is passed straight
        through to the page write as its write mask: masked lanes (idle
        slots, padding sub-steps of a mixed prefill+decode chunk) are
        redirected into the reserved null page 0 and never claim a page
        scale — a masked lane can never scribble into a real page, which
        prefix caching requires (mapped prefix pages are shared
        read-only between slots).
      * **Position-addressed stochastic streams.**  The layer's PRNG key
        is folded with each slot's *write position*, so the rounding bits
        of a KV write depend only on (layer, position) — never on the
        engine step or batch composition.  Page codes are therefore a
        pure function of the token content that produced them, which is
        what makes a prefix-cache hit bit-identical to recomputing the
        prefix (tests/test_prefix_cache.py).
    """
    B = x.shape[0]
    KV = cfg.n_kv_heads
    pol = cfg.policy
    positions = jnp.asarray(paged["lengths"], jnp.int32)[:, None]
    q, k_new, v_new = _gqa_qkv(p, x, cfg, positions, use_rope, site=site)
    # Serving TP: heads/KV-groups shard over the model axis (per-group
    # attention concatenates across shards — no cross-shard reduction).
    # Roles resolve only inside the engine's hint context; no-ops otherwise.
    q = hint(q, "tp_heads")
    k_new = hint(k_new, "tp_kv")
    v_new = hint(v_new, "tp_kv")
    out, new_cache = _paged_write_attend(
        q, k_new, v_new, cache, paged, pol, n_kv_heads=KV,
        window=0 if is_global else cfg.window, cap=cfg.attn_softcap,
        site=site)
    # All-gather the head-sharded output BEFORE the wo contraction: the
    # matmul then sees the whole array on every shard, so TP introduces no
    # partial sums and the token stream stays bit-identical to TP=1.
    out = hint(out, "tp_gather")
    y = qlinear(out.reshape(B, 1, -1), p["wo"], pol, site=f"{site}.wo")
    return y, new_cache


def _paged_write_attend(q, k_new, v_new, cache, paged, pol, *, n_kv_heads,
                        window=0, cap=0.0, scale=None, site=""):
    """Write one token's K and V rows [B, 1, KV, d] into their pages and
    attend (the paged decode of :func:`gqa_decode_paged` and
    :func:`mla_decode_paged`).  The layer's PRNG key is folded with each
    slot's write position; ``paged["active"]`` is the write mask.  Fused:
    one launch, bit-identical to the unfused write-then-attend on active
    lanes.  Returns (out [B, 1, H, dv], new page arrays)."""
    lengths = jnp.asarray(paged["lengths"], jnp.int32)
    block_tables = jnp.asarray(paged["block_tables"], jnp.int32)
    page_size = paged["page_size"]
    active = paged.get("active")
    key = paged.get("key")
    if key is None:
        kk = vk = None
    else:
        kk, vk = tuple(jax.random.split(key))
        fold_pos = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
        kk, vk = fold_pos(kk, lengths), fold_pos(vk, lengths)
    if paged.get("fused", True):
        out, kp, ks, vp, vs = numerics.kv_fused_write_attend(
            q, k_new[:, 0], v_new[:, 0], cache["kp"], cache["vp"],
            cache["ks"], cache["vs"], block_tables, lengths, pol,
            n_kv_heads=n_kv_heads, k_key=kk, v_key=vk, write_mask=active,
            window=window, cap=cap, scale=scale, site=site,
        )
    else:
        logical = lengths // page_size
        page_ids = jnp.take_along_axis(
            block_tables, logical[:, None], axis=1)[:, 0]
        rows = lengths - logical * page_size
        kp, ks = numerics.kv_write_token(pol, cache["kp"], cache["ks"],
                                         k_new[:, 0], page_ids, rows, key=kk,
                                         write_mask=active)
        vp, vs = numerics.kv_write_token(pol, cache["vp"], cache["vs"],
                                         v_new[:, 0], page_ids, rows, key=vk,
                                         write_mask=active)
        out = numerics.attention(
            q, kp, vp, ks, vs, block_tables, lengths + 1, pol,
            n_kv_heads=n_kv_heads, window=window, cap=cap,
            scale=scale, site=site,
        )
    return out, {"kp": kp, "vp": vp, "ks": ks, "vs": vs}


# --------------------------------------------------------------------------- #
# MLA (DeepSeek-V2)
# --------------------------------------------------------------------------- #
def mla_init(rng, cfg):
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, L = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dt = cfg.pdtype
    ks = jax.random.split(rng, 5)
    return {
        "wq": _init(ks[0], (D, H * (dn + dr)), dt),
        "w_dkv": _init(ks[1], (D, L + dr), dt),
        "kv_norm": jnp.zeros((L,), dt),
        "w_uk": _init(ks[2], (L, H * dn), dt),
        "w_uv": _init(ks[3], (L, H * dv), dt),
        "wo": _init(ks[4], (H * dv, D), dt),
    }


def mla_softmax_scale(cfg) -> float:
    """The published softmax scale of MLA: ``(nope + rope) ** -0.5``, times
    ``mscale**2`` of ``mscale_all_dim`` under YaRN."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        m = yarn_mscale(y.factor, y.mscale_all_dim)
        scale *= m * m
    return scale


def _mla_q(p, x, cfg, positions, site="blocks.*.attn"):
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = qlinear(x, p["wq"], cfg.policy, site=f"{site}.wq").reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = rope(q_pe, positions, cfg.rope_theta, cfg.yarn)
    return q_nope, q_pe


def _mla_latent(p, x, cfg, positions, site="blocks.*.attn"):
    B, S, D = x.shape
    L, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    dkv = qlinear(x, p["w_dkv"], cfg.policy, site=f"{site}.w_dkv")
    ckv = rms_norm(dkv[..., :L], p["kv_norm"], cfg.norm_eps)
    kpe = rope(dkv[..., L:].reshape(B, S, 1, dr), positions, cfg.rope_theta,
               cfg.yarn)
    return ckv, kpe.reshape(B, S, dr)


def _mla_absorbed(p, name, cfg, dtype, site):
    """``w_uk`` or ``w_uv`` as [L, H, d] float32, for the absorbed form."""
    from .quantize import resolve_weight

    H, L = cfg.n_heads, cfg.kv_lora_rank
    w = resolve_weight(p[name], numerics.weight_format(cfg.policy,
                                                       f"{site}.{name}"),
                       dtype)
    return w.reshape(L, H, -1).astype(jnp.float32)


def mla_forward(p, x, cfg, *, positions, q_chunk=512, kv_chunk=1024,
                site="blocks.*.attn", **_):
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv, L = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pol = cfg.policy
    q_nope, q_pe = _mla_q(p, x, cfg, positions, site=site)
    ckv, kpe = _mla_latent(p, x, cfg, positions, site=site)
    # Expanded keys/values (train/prefill path)
    k_nope = qlinear(ckv, p["w_uk"], pol, site=f"{site}.w_uk").reshape(B, S, H, dn)
    v = qlinear(ckv, p["w_uv"], pol, site=f"{site}.w_uv").reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kpe[:, :, None, :], (B, S, H, dr))], axis=-1)
    out = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk,
                            kv_chunk=kv_chunk, scale=mla_softmax_scale(cfg))
    y = qlinear(out.reshape(B, S, -1), p["wo"], pol, site=f"{site}.wo")
    # cache representation must match the decode path: FP8 codes when the
    # KV cache is quantized (a raw float here would be garbage-cast to
    # uint8 by the serving splice)
    return y, {"ckv": _kv_store(ckv, cfg), "kpe": _kv_store(kpe, cfg)}


def mla_decode(p, x, cfg, *, cache, pos, site="blocks.*.attn", **_):
    """Absorbed-matrices decode: attention directly in the latent space.

    ``pos`` is a scalar or a [B] vector of per-slot positions."""
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv, L = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q_nope, q_pe = _mla_q(p, x, cfg, positions, site=site)  # [B,1,H,dn],[B,1,H,dr]
    ckv_new, kpe_new = _mla_latent(p, x, cfg, positions, site=site)
    if _kv_fp8(cfg):
        ckv_new, kpe_new = _kv_store(ckv_new, cfg), _kv_store(kpe_new, cfg)
    else:
        ckv_new = ckv_new.astype(cache["ckv"].dtype)
        kpe_new = kpe_new.astype(cache["kpe"].dtype)
    b_ix = jnp.arange(B)
    ckv = cache["ckv"].at[b_ix, pos].set(ckv_new[:, 0])
    kpe = cache["kpe"].at[b_ix, pos].set(kpe_new[:, 0])
    cache = {"ckv": ckv, "kpe": kpe}
    ckv, kpe = _kv_load(ckv, cfg), _kv_load(kpe, cfg)
    S = ckv.shape[1]

    # absorb: q_eff[b,h,l] = sum_d q_nope[b,h,d] * w_uk[l,h,d]
    q_eff = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(jnp.float32),
                       _mla_absorbed(p, "w_uk", cfg, x.dtype, site))
    s = jnp.einsum("bhl,bsl->bhs", q_eff, ckv.astype(jnp.float32))
    s = s + jnp.einsum("bhr,bsr->bhs", q_pe[:, 0].astype(jnp.float32),
                       kpe.astype(jnp.float32))
    s = s * mla_softmax_scale(cfg)
    t = jnp.arange(S)
    s = jnp.where((t[None, :] <= pos[:, None])[:, None, :], s, -2.0e30)
    m = s.max(-1, keepdims=True)
    pattn = jnp.exp(s - m)
    den = pattn.sum(-1, keepdims=True)
    lat = jnp.einsum("bhs,bsl->bhl", pattn / jnp.maximum(den, 1e-37),
                     ckv.astype(jnp.float32))
    out = jnp.einsum("bhl,lhv->bhv", lat,
                     _mla_absorbed(p, "w_uv", cfg, x.dtype, site))
    y = qlinear(out.reshape(B, 1, H * dv).astype(x.dtype), p["wo"], cfg.policy,
                site=f"{site}.wo")
    return y, cache


def mla_decode_paged(p, x, cfg, *, cache, paged, site="blocks.*.attn"):
    """Absorbed MLA decode against the global page pool (serving path).

    cache: this layer's page arrays {"kp": rope keys [P, page, dr], "vp":
    latents [P, page, L], "ks", "vs": [P] f32}; ``paged`` as in
    :func:`gqa_decode_paged`, whose two serving contracts (explicit write
    mask, position-addressed stochastic streams) hold here too.  The new
    token's rope key and latent are written into their pages (a fresh page
    takes a pow2 scale from its first row), and the paged kernel's MLA
    mode attends: one KV head, the 16 query heads as its group, query
    ``[q_pe ‖ q_eff]`` with ``q_eff = q_nope · W_uk``, values the latents.
    The [B, H, L] latent output maps through ``W_uv`` and ``wo``.
    Returns (y, new_cache).
    """
    B = x.shape[0]
    H, dv = cfg.n_heads, cfg.v_head_dim
    pol = cfg.policy
    positions = jnp.asarray(paged["lengths"], jnp.int32)[:, None]
    q_nope, q_pe = _mla_q(p, x, cfg, positions, site=site)  # [B,1,H,dn|dr]
    ckv_new, kpe_new = _mla_latent(p, x, cfg, positions, site=site)
    q_eff = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(jnp.float32),
                       _mla_absorbed(p, "w_uk", cfg, x.dtype, site))
    q = jnp.concatenate([q_pe[:, 0].astype(jnp.float32), q_eff],
                        axis=-1)[:, None]                  # [B,1,H,dr+L]
    # the token's rows [B, 1, 1, dr|L]: the kernel's single KV head
    out, new_cache = _paged_write_attend(
        q, kpe_new[:, :, None], ckv_new[:, :, None], cache, paged, pol,
        n_kv_heads=1, scale=mla_softmax_scale(cfg), site=site)
    o = jnp.einsum("bhl,lhv->bhv", out[:, 0].astype(jnp.float32),
                   _mla_absorbed(p, "w_uv", cfg, x.dtype, site))
    y = qlinear(o.reshape(B, 1, H * dv).astype(x.dtype), p["wo"], pol,
                site=f"{site}.wo")
    return y, new_cache
