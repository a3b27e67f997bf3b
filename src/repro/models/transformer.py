"""Transformer stack assembly: scan-over-blocks, all families.

Layers repeat in a static *pattern* of length P (1 for uniform stacks, 2 for
gemma2 local/global, 6 for gemma3, 8 for jamba); parameters are stacked
[n_blocks, ...] and the stack is a single ``lax.scan`` over blocks — compile
time is O(P), not O(n_layers).  ``first_dense`` prefix layers (deepseek)
live outside the scan.

Modes:
  * train/prefill: full-sequence forward; prefill also emits the KV cache.
  * decode: one token against a full-length cache (``pos`` = write index).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.hints import hint
from .attention import (
    gqa_decode,
    gqa_decode_paged,
    gqa_forward,
    gqa_init,
    mla_decode,
    mla_decode_paged,
    mla_forward,
    mla_init,
)
from .layers import gated_mlp, qlinear, rms_norm
from .mamba2 import mamba2_init, ssd_decode, ssd_forward
from .moe import moe_ffn, moe_init, moe_serve


@dataclasses.dataclass(frozen=True)
class SubSpec:
    mixer: str  # "attn" | "mamba"
    attn_global: bool = True
    ffn: str = "mlp"  # "mlp" | "moe" | "none"
    cross: bool = False  # enc-dec cross attention after self attention
    causal: bool = True


def layer_specs(cfg) -> Tuple[List[SubSpec], List[SubSpec], int]:
    """(prefix_specs, pattern_specs, n_blocks)."""
    P = cfg.layer_pattern_period
    n_prefix = cfg.first_dense
    body = cfg.n_layers - n_prefix
    assert body % P == 0, (cfg.name, body, P)

    def spec(i: int) -> SubSpec:
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        ffn = "none" if cfg.d_ff == 0 and not cfg.is_moe_layer(i) else (
            "moe" if cfg.is_moe_layer(i) else "mlp"
        )
        return SubSpec(
            mixer=mixer,
            attn_global=cfg.is_global_attn_layer(i),
            ffn=ffn,
            cross=(cfg.family == "encdec"),
            causal=True,
        )

    prefix = [dataclasses.replace(spec(i), ffn="mlp") for i in range(n_prefix)]
    pattern = [spec(n_prefix + j) for j in range(P)]
    return prefix, pattern, body // P


# --------------------------------------------------------------------------- #
# Per-sublayer init / forward / decode
# --------------------------------------------------------------------------- #
def _mlp_init(rng, cfg, d_ff):
    D = cfg.d_model
    dt = cfg.pdtype
    ks = jax.random.split(rng, 3)
    s = 0.02
    return {
        "w_gate": (jax.random.normal(ks[0], (D, d_ff), jnp.float32) * s).astype(dt),
        "w_up": (jax.random.normal(ks[1], (D, d_ff), jnp.float32) * s).astype(dt),
        "w_down": (jax.random.normal(ks[2], (d_ff, D), jnp.float32) * s).astype(dt),
    }


def sublayer_init(rng, cfg, spec: SubSpec):
    D = cfg.d_model
    dt = cfg.pdtype
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"ln1": jnp.zeros((D,), dt)}
    if spec.mixer == "attn":
        p["attn"] = mla_init(ks[0], cfg) if cfg.attn_impl == "mla" else gqa_init(ks[0], cfg)
    else:
        p["mamba"] = mamba2_init(ks[0], cfg)
    if spec.cross:
        p["ln_x"] = jnp.zeros((D,), dt)
        p["cross"] = gqa_init(ks[1], cfg)
    if spec.ffn != "none":
        p["ln2"] = jnp.zeros((D,), dt)
        p["ffn"] = moe_init(ks[2], cfg) if spec.ffn == "moe" else _mlp_init(ks[2], cfg, cfg.d_ff)
    if cfg.sandwich_norm:
        p["ln1_post"] = jnp.zeros((D,), dt)
        if spec.ffn != "none":
            p["ln2_post"] = jnp.zeros((D,), dt)
    return p


def _use_rope(cfg) -> bool:
    return cfg.family != "encdec"


def sublayer_forward(p, spec: SubSpec, x, cfg, *, positions, mode,
                     enc_out=None, aux=None, site="blocks.*"):
    """Full-sequence sublayer.  Returns (x, cache_entry, aux).

    ``site`` names this sublayer for per-site numerics-policy overrides
    (e.g. ``"blocks.0"`` — the index within the scan pattern is static,
    the scanned block index is the wildcard)."""
    cache: Dict[str, Any] = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.mixer == "attn":
        if cfg.attn_impl == "mla":
            out, c = mla_forward(p["attn"], h, cfg, positions=positions,
                                 site=f"{site}.attn")
        else:
            out, c = gqa_forward(
                p["attn"], h, cfg, is_global=spec.attn_global,
                positions=positions, causal=spec.causal, use_rope=_use_rope(cfg),
                site=f"{site}.attn",
            )
        cache["self"] = c
    else:
        out, c = ssd_forward(p["mamba"], h, cfg)
        cache["self"] = c
    if cfg.sandwich_norm:
        out = rms_norm(out, p["ln1_post"], cfg.norm_eps)
    x = x + out

    if spec.cross:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        # cross K/V from encoder output (cached for decode)
        from .attention import _gqa_qkv

        _, xk, xv = _gqa_qkv(
            p["cross"], enc_out, cfg,
            jnp.zeros(enc_out.shape[:2], jnp.int32), use_rope=False,
        )
        out, _ = gqa_forward(
            p["cross"], h, cfg, is_global=True, positions=positions,
            cross_kv=(xk, xv), use_rope=False, site=f"{site}.cross",
        )
        cache["xk"], cache["xv"] = xk, xv
        x = x + out

    if spec.ffn != "none":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.ffn == "moe":
            out, moe_aux = moe_ffn(p["ffn"], h, cfg, site=f"{site}.ffn")
            if aux is not None:
                aux = {k: aux[k] + moe_aux[k] for k in aux}
        else:
            out = gated_mlp(h, p["ffn"], cfg.policy, cfg.act_fn,
                            site=f"{site}.ffn")
        if cfg.sandwich_norm:
            out = rms_norm(out, p["ln2_post"], cfg.norm_eps)
        x = x + out
    return x, cache, aux


def sublayer_decode(p, spec: SubSpec, x, cfg, *, cache, pos, aux=None,
                    paged=None, site="blocks.*"):
    """Single-token sublayer.  Returns (x, new_cache, aux).

    ``pos`` is a scalar or per-slot [B] vector.  ``paged`` is the serving
    step's shared paged-cache state (block tables, lengths, page size, PRNG
    key) — attention sublayers whose cache entry is paged (has "kp") route
    through the page pool; everything else uses the dense slot cache.
    ``aux`` carries the step's routing counts (:func:`serve_aux0`); expert
    layers run the dropless served dispatch.
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    active = None if paged is None else paged.get("active")
    is_paged = paged is not None and "kp" in cache["self"]
    if spec.mixer == "attn":
        if cfg.attn_impl == "mla" and is_paged:
            out, c = mla_decode_paged(p["attn"], h, cfg, cache=cache["self"],
                                      paged=paged, site=f"{site}.attn")
        elif cfg.attn_impl == "mla":
            out, c = mla_decode(p["attn"], h, cfg, cache=cache["self"],
                                pos=pos, site=f"{site}.attn")
        elif is_paged:
            out, c = gqa_decode_paged(
                p["attn"], h, cfg, is_global=spec.attn_global,
                cache=cache["self"], paged=paged, use_rope=_use_rope(cfg),
                site=f"{site}.attn",
            )
        else:
            out, c = gqa_decode(
                p["attn"], h, cfg, is_global=spec.attn_global,
                cache=cache["self"], pos=pos, use_rope=_use_rope(cfg),
                site=f"{site}.attn",
            )
    else:
        out, c = ssd_decode(p["mamba"], h, cfg, cache["self"])
    if active is not None and "kp" not in c:
        # masked sub-step of a mixed prefill+decode batch: dense per-slot
        # entries (SSM states, dense KV rows) of inactive slots must not
        # advance — keep the old entry for them.  Paged entries need no
        # select: their inactive writes already went to the null page.
        c = jax.tree.map(
            lambda nv, ov: jnp.where(
                active.reshape((-1,) + (1,) * (nv.ndim - 1)), nv, ov
            ),
            c, cache["self"],
        )
    new_cache["self"] = c
    if cfg.sandwich_norm:
        out = rms_norm(out, p["ln1_post"], cfg.norm_eps)
    x = x + out

    if spec.cross:
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        out, _ = gqa_decode(
            p["cross"], h, cfg, is_global=True, cache=None, pos=pos,
            cross_kv=(cache["xk"], cache["xv"]), use_rope=False,
            site=f"{site}.cross",
        )
        new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        x = x + out

    if spec.ffn != "none":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.ffn == "moe":
            out, counts = moe_serve(p["ffn"], h, cfg, valid=active,
                                    site=f"{site}.ffn")
            if aux is not None:
                aux = {k: aux[k] + counts[k] for k in aux}
        else:
            out = gated_mlp(h, p["ffn"], cfg.policy, cfg.act_fn,
                            site=f"{site}.ffn")
        if cfg.sandwich_norm:
            out = rms_norm(out, p["ln2_post"], cfg.norm_eps)
        x = x + out
    return x, new_cache, aux


# --------------------------------------------------------------------------- #
# Stack: scan over blocks of P sublayers
# --------------------------------------------------------------------------- #
AUX0 = {"moe_lb": 0.0, "moe_z": 0.0}


def serve_aux0():
    """Zero routing counts of a served step: assignments of the step's
    valid tokens to held and to absent routed experts, over its layers."""
    return {"moe_held": jnp.zeros((), jnp.int32),
            "moe_absent": jnp.zeros((), jnp.int32)}


def stack_init(rng, cfg, pattern: List[SubSpec], n_blocks: int):
    """Stacked block params: vmap the per-block init over n_blocks rngs."""

    def block_init(r):
        ks = jax.random.split(r, len(pattern))
        return tuple(sublayer_init(k, cfg, s) for k, s in zip(ks, pattern))

    return jax.vmap(block_init)(jax.random.split(rng, n_blocks))


def stack_forward(blocks, x, cfg, pattern, *, positions, mode,
                  enc_out=None, remat=True):
    """Returns (x, stacked_caches_or_None, aux)."""
    want_cache = mode == "prefill"

    def block_fn(carry, bp):
        x, aux = carry
        x = hint(x, "act")
        caches = []
        for j, spec in enumerate(pattern):
            x, c, aux = sublayer_forward(
                bp[j], spec, x, cfg, positions=positions, mode=mode,
                enc_out=enc_out, aux=aux, site=f"blocks.{j}",
            )
            caches.append(c)
        return (x, aux), tuple(caches) if want_cache else None

    fn = block_fn
    if remat and mode == "train":
        policy = (
            jax.checkpoint_policies.dots_saveable
            if cfg.remat_policy == "dots"
            else jax.checkpoint_policies.nothing_saveable
        )
        fn = jax.checkpoint(block_fn, policy=policy)
    (x, aux), caches = jax.lax.scan(fn, (x, dict(AUX0)), blocks)
    return x, caches, aux


def stack_decode(blocks, caches, x, cfg, pattern, *, pos, paged=None):
    key = None if paged is None else paged.get("key")
    n_blocks = jax.tree_util.tree_leaves(caches)[0].shape[0]
    # per-block stochastic-write keys ride the scan as an xs array (a dummy
    # when stochastic rounding is off, to keep the scan structure static)
    keys = (
        jax.random.split(key, n_blocks)
        if key is not None
        else jnp.zeros((n_blocks, 2), jnp.uint32)
    )

    def block_fn(carry, scanned):
        x, aux, caches = carry
        x = hint(x, "act")
        bp, i, bkey = scanned
        bc = jax.tree.map(lambda a: a[i], caches)
        new_cs = []
        for j, spec in enumerate(pattern):
            bpaged = None
            if paged is not None:
                bkj = jax.random.fold_in(bkey, j) if key is not None else None
                bpaged = dict(paged, key=bkj)
            x, c, aux = sublayer_decode(
                bp[j], spec, x, cfg, cache=bc[j], pos=pos, aux=aux,
                paged=bpaged, site=f"blocks.{j}",
            )
            new_cs.append(c)
        caches = jax.tree.map(lambda a, c: a.at[i].set(c), caches,
                              tuple(new_cs))
        return (x, aux, caches), None

    (x, aux, new_caches), _ = jax.lax.scan(
        block_fn, (x, serve_aux0(), caches),
        (blocks, jnp.arange(n_blocks), keys)
    )
    return x, new_caches, aux
