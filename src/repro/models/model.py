"""Top-level model API: init / loss / prefill / decode for every family.

``Model(cfg, max_seq)`` wraps the scan-based stack with embeddings, the
whisper encoder, the llava patch-embedding projector, the LM head and the
loss.  All methods are pure functions of (params, inputs) — directly
jit/pjit-able, and shape-only traceable with jax.eval_shape for the
512-device dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.hints import hint
from .layers import rms_norm, softcap
from .transformer import (
    AUX0,
    SubSpec,
    serve_aux0,
    layer_specs,
    stack_decode,
    stack_forward,
    stack_init,
    sublayer_decode,
    sublayer_forward,
    sublayer_init,
)

NEG = -1.0e30


class Model:
    def __init__(self, cfg, max_seq: int = 0):
        self.cfg = cfg
        self.max_seq = max_seq
        self.prefix_specs, self.pattern, self.n_blocks = layer_specs(cfg)
        if cfg.family == "encdec":
            self.enc_pattern = [
                SubSpec(mixer="attn", attn_global=True, ffn="mlp", cross=False, causal=False)
            ]

    # ------------------------------------------------------------------ #
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(rng, 8)
        D, Vp = cfg.d_model, cfg.vocab_padded
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(ks[0], (Vp, D), jnp.float32) * 0.02).astype(cfg.pdtype),
            "blocks": stack_init(ks[1], cfg, self.pattern, self.n_blocks),
            "final_norm": jnp.zeros((D,), cfg.pdtype),
        }
        if self.prefix_specs:
            pk = jax.random.split(ks[2], len(self.prefix_specs))
            params["prefix"] = tuple(
                sublayer_init(k, cfg, s) for k, s in zip(pk, self.prefix_specs)
            )
        if not cfg.tie_embeddings:
            params["unembed"] = (
                jax.random.normal(ks[3], (D, Vp), jnp.float32) * 0.02
            ).astype(cfg.pdtype)
        if cfg.family == "encdec":
            params["enc_blocks"] = stack_init(ks[4], cfg, self.enc_pattern, cfg.n_enc_layers)
            params["enc_pos"] = (
                jax.random.normal(ks[5], (cfg.enc_context, D), jnp.float32) * 0.02
            ).astype(cfg.pdtype)
            assert self.max_seq > 0, "encdec needs max_seq for learned positions"
            params["dec_pos"] = (
                jax.random.normal(ks[6], (self.max_seq, D), jnp.float32) * 0.02
            ).astype(cfg.pdtype)
            params["enc_final_norm"] = jnp.zeros((D,), cfg.pdtype)
        if cfg.family == "vlm":
            params["img_proj"] = (
                jax.random.normal(ks[7], (D, D), jnp.float32) * 0.02
            ).astype(cfg.pdtype)
        return params

    # ------------------------------------------------------------------ #
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.emb_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        return x

    def _unembed(self, params, x):
        cfg = self.cfg
        w = params.get("unembed")
        if w is not None:
            from .. import numerics
            from .quantize import resolve_weight

            w = resolve_weight(
                w, numerics.weight_format(cfg.policy, "unembed"), x.dtype
            )
        logits = (x @ w if w is not None else x @ params["embed"].T).astype(jnp.float32)
        # 3-D train/prefill logits use the training role; 2-D decode logits
        # get their own role so the serving engine can pin them
        # vocab-column-sharded (a pure concatenation across shards).
        logits = hint(logits, "logits" if logits.ndim == 3 else "logits_decode")
        logits = softcap(logits, cfg.final_softcap)
        if cfg.vocab_padded > cfg.vocab:
            mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
            logits = jnp.where(mask, logits, NEG)
        return logits

    def _encode(self, params, frames):
        """Whisper encoder over precomputed frame embeddings [B, T, D]."""
        cfg = self.cfg
        x = frames.astype(cfg.pdtype) + params["enc_pos"][None]
        pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])
        x, _, _ = stack_forward(
            params["enc_blocks"], x, cfg, self.enc_pattern,
            positions=pos, mode="train", remat=False,
        )
        return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)

    def _assemble_inputs(self, params, batch, mode):
        """Returns (x, positions, enc_out, labels, mask)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, tokens)
        labels = batch.get("labels")
        enc_out = None
        if cfg.family == "vlm":
            img = batch["img"].astype(x.dtype) @ params["img_proj"]
            x = jnp.concatenate([img, x], axis=1)
            if labels is not None:
                pad = jnp.full((B, cfg.n_img_tokens), -1, labels.dtype)
                labels = jnp.concatenate([pad, labels], axis=1)
        elif cfg.family == "encdec":
            enc_out = self._encode(params, batch["frames"])
            x = x + params["dec_pos"][None, :S]
        x = hint(x, "act")
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return x, positions, enc_out, labels

    def _run_prefix(self, params, x, positions, mode, enc_out):
        caches = []
        aux = dict(AUX0)
        for i, (p, s) in enumerate(
            zip(params.get("prefix", ()), self.prefix_specs)
        ):
            x, c, aux = sublayer_forward(
                p, s, x, self.cfg, positions=positions, mode=mode,
                enc_out=enc_out, aux=aux, site=f"prefix.{i}",
            )
            caches.append(c)
        return x, tuple(caches), aux

    # ------------------------------------------------------------------ #
    def loss_fn(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        x, positions, enc_out, labels = self._assemble_inputs(params, batch, "train")
        x, _, aux0 = self._run_prefix(params, x, positions, "train", enc_out)
        x, _, aux = stack_forward(
            params["blocks"], x, cfg, self.pattern,
            positions=positions, mode="train", enc_out=enc_out,
        )
        aux = {k: aux[k] + aux0[k] for k in aux}
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)

        mask = (labels >= 0) & (jnp.arange(x.shape[1])[None, :] < x.shape[1] - 1)
        safe_labels = jnp.maximum(labels, 0)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
        ce = jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1)
        loss = ce + 0.01 * aux["moe_lb"] + 1e-3 * aux["moe_z"]
        return loss, {"ce": ce, "moe_lb": aux["moe_lb"], "moe_z": aux["moe_z"]}

    # ------------------------------------------------------------------ #
    def prefill(self, params, batch):
        cfg = self.cfg
        x, positions, enc_out, _ = self._assemble_inputs(params, batch, "prefill")
        x, pc, _ = self._run_prefix(params, x, positions, "prefill", enc_out)
        x, caches, _ = stack_forward(
            params["blocks"], x, cfg, self.pattern,
            positions=positions, mode="prefill", enc_out=enc_out, remat=False,
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x[:, -1])
        return logits, {"prefix": pc, "blocks": caches}

    def decode_step(self, params, cache, tokens, pos):
        """tokens: [B] int32; pos: scalar int32 write index, or a [B]
        vector of per-slot positions (serving batches where slots sit at
        different context lengths)."""
        cfg = self.cfg
        B = tokens.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        x = self._embed(params, tokens[:, None])
        if cfg.family == "encdec":
            x = x + jnp.take(params["dec_pos"], pos, axis=0)[:, None]
        aux = serve_aux0()
        new_prefix = []
        for i, (p, s, c) in enumerate(zip(
            params.get("prefix", ()), self.prefix_specs, cache.get("prefix", ())
        )):
            x, nc, aux = sublayer_decode(p, s, x, cfg, cache=c, pos=pos,
                                         aux=aux, site=f"prefix.{i}")
            new_prefix.append(nc)
        x, new_caches, _ = stack_decode(
            params["blocks"], cache["blocks"], x, cfg, self.pattern, pos=pos
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x[:, 0])
        return logits, {"prefix": tuple(new_prefix), "blocks": new_caches}

    def decode_step_paged(self, params, cache, tokens, lengths, block_tables,
                          *, page_size: int, key=None, active=None,
                          fused: bool = True):
        """One decode step against the paged cache (serving path).

        tokens: [B] int32; lengths: [B] int32 per-slot context lengths
        (BEFORE this token); block_tables: [B, maxp] int32 page ids;
        ``key``: PRNG key for stochastic-rounding KV writes (None =>
        deterministic writes in cfg.quant.mode) — folded with each slot's
        write position inside the attention layer, never with the engine
        step, so page codes are reproducible functions of content;
        ``active``: optional [B] bool write mask — idle slots' page
        writes land in the reserved null page and their dense cache
        entries are kept, so a slot whose block table still maps shared
        prefix pages can never corrupt them.  Attention layers (GQA K/V,
        MLA latents) read/write the page pool; SSM/cross entries keep their
        dense slot caches, indexed by per-slot positions.  Returns
        (logits, new_cache).
        """
        logits, cache, _ = self._paged_token_step(
            params, cache, tokens, lengths, block_tables,
            page_size=page_size, key=key, active=active, fused=fused,
        )
        return logits, cache

    def _paged_token_step(self, params, cache, tokens, lengths, block_tables,
                          *, page_size: int, key, active, fused: bool = True):
        """Shared body of the paged decode/mixed steps.

        ``active`` is None (every slot live — the plain decode path, traced
        without any masking ops) or a [B] bool vector: inactive slots'
        page writes are redirected to the reserved null page and their dense
        cache entries (SSM states) are kept unchanged, so a masked sub-step
        is a no-op for them.

        Returns (logits, new_cache, routing counts of the active lanes:
        int32 [2], assignments to held and to absent routed experts).
        """
        cfg = self.cfg
        B = tokens.shape[0]
        lengths = jnp.asarray(lengths, jnp.int32)
        paged = {
            "block_tables": jnp.asarray(block_tables, jnp.int32),
            "lengths": lengths,
            "page_size": page_size,
            "key": key,
            "active": active,
            "fused": fused,
        }
        x = self._embed(params, tokens[:, None])
        if cfg.family == "encdec":
            x = x + jnp.take(params["dec_pos"], lengths, axis=0)[:, None]
        aux = serve_aux0()
        new_prefix = []
        for i, (p, s, c) in enumerate(zip(
            params.get("prefix", ()), self.prefix_specs, cache.get("prefix", ())
        )):
            pkey = None if key is None else jax.random.fold_in(key, 1 + i)
            x, nc, aux = sublayer_decode(
                p, s, x, cfg, cache=c, pos=lengths, aux=aux,
                paged=dict(paged, key=pkey), site=f"prefix.{i}",
            )
            new_prefix.append(nc)
        bkey = None if key is None else jax.random.fold_in(key, 0)
        x, new_caches, baux = stack_decode(
            params["blocks"], cache["blocks"], x, cfg, self.pattern,
            pos=lengths, paged=dict(paged, key=bkey),
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x[:, 0])
        counts = jnp.stack([aux["moe_held"] + baux["moe_held"],
                            aux["moe_absent"] + baux["moe_absent"]])
        return (logits, {"prefix": tuple(new_prefix), "blocks": new_caches},
                counts)

    def step_paged(self, params, cache, tokens, lengths, n_new, block_tables,
                   *, page_size: int, key=None, fused: bool = True):
        """Mixed prefill+decode step over the paged cache (the continuous
        scheduler's model call).

        tokens: [B, T] int32 — up to T new tokens per slot; lengths: [B]
        int32 context length BEFORE the step; n_new: [B] int32 valid-token
        count per row (0 = idle slot, 1 = a decode step, >1 = a prefill
        chunk); block_tables: [B, maxp] int32.

        Internally scans T single-token sub-steps with per-slot active
        masks (the **explicit write mask** of the page writes): sub-step t
        processes ``tokens[:, t]`` at position ``lengths + t`` for slots
        with ``t < n_new``.  Inactive slots' page writes land in the
        reserved null page and their dense cache rows are kept via a
        select, so a decode slot (1 valid token) and a mid-prefill slot
        (T valid tokens) coexist in one jitted call — chunked prefill
        never blocks decode, and a slot whose block table maps shared
        prefix pages can never scribble into them from a masked lane.
        The caller must have allocated pages for ``lengths + n_new``
        tokens per slot.

        ``key`` is ONE stream key for the whole chunk — every sub-step
        sees the same key, and the attention layer folds each slot's
        write position into it.  Stochastic KV rounding is therefore
        addressed by (layer, position), never by the sub-step index or
        the engine step, which keeps page codes a pure function of
        content (the prefix-cache bit-identity contract).

        Returns (logits [B, vocab_padded] of each slot's LAST valid token —
        zeros for idle slots —, the new cache, and the step's routing
        counts int32 [2]: its valid tokens' assignments to held and to
        absent routed experts, summed over the expert layers).
        """
        cfg = self.cfg
        B, T = tokens.shape
        tokens = jnp.asarray(tokens, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        n_new = jnp.asarray(n_new, jnp.int32)
        last0 = jnp.zeros((B, cfg.vocab_padded), jnp.float32)

        def body(carry, scanned):
            cache, last, counts = carry
            t, toks_t = scanned
            act = t < n_new
            pos = lengths + jnp.minimum(t, jnp.maximum(n_new - 1, 0))
            logits, cache, c = self._paged_token_step(
                params, cache, toks_t, pos, block_tables,
                page_size=page_size, key=key, active=act, fused=fused,
            )
            last = jnp.where(act[:, None], logits, last)
            return (cache, last, counts + c), None

        (cache, last, counts), _ = jax.lax.scan(
            body, (cache, last0, jnp.zeros((2,), jnp.int32)),
            (jnp.arange(T), tokens.T)
        )
        return last, cache, counts

    # ------------------------------------------------------------------ #
    def _entry_cache(self, spec: SubSpec, B: int, S: int):
        from .. import numerics

        cfg = self.cfg
        dt = jnp.uint8 if numerics.kv_quantized(cfg.policy) else cfg.pdtype
        e: Dict[str, Any] = {}
        if spec.mixer == "attn":
            if cfg.attn_impl == "mla":
                e["self"] = {
                    "ckv": jnp.zeros((B, S, cfg.kv_lora_rank), dt),
                    "kpe": jnp.zeros((B, S, cfg.qk_rope_dim), dt),
                }
            else:
                # sliding-window layers never attend past `window`: keep a
                # ring buffer of that length (keys carry rope from their
                # absolute position, so slot order is irrelevant).
                S_eff = S
                if cfg.window and not spec.attn_global:
                    S_eff = min(S, cfg.window)
                kvshape = (B, S_eff, cfg.n_kv_heads, cfg.hd)
                e["self"] = {"k": jnp.zeros(kvshape, dt), "v": jnp.zeros(kvshape, dt)}
        else:
            from .mamba2 import dims

            di, nh, P, N = dims(cfg)
            e["self"] = {
                "conv": jnp.zeros((B, cfg.ssm_conv_width - 1, di + 2 * N), cfg.pdtype),
                "state": jnp.zeros((B, nh, P, N), jnp.float32),
            }
        if spec.cross:
            xshape = (B, cfg.enc_context, cfg.n_kv_heads, cfg.hd)
            e["xk"] = jnp.zeros(xshape, cfg.pdtype)
            e["xv"] = jnp.zeros(xshape, cfg.pdtype)
        return e

    def make_cache(self, B: int, S: int):
        """Zero-filled decode cache (shape source for the dry-run specs)."""
        prefix = tuple(self._entry_cache(s, B, S) for s in self.prefix_specs)
        one_block = tuple(self._entry_cache(s, B, S) for s in self.pattern)
        blocks = jax.tree.map(
            lambda a: jnp.zeros((self.n_blocks,) + a.shape, a.dtype), one_block
        )
        return {"prefix": prefix, "blocks": blocks}

    def _entry_cache_paged(self, spec: SubSpec, B: int, S: int,
                           num_pages: int, page_size: int):
        """Per-layer paged entry: attention state lives in the global page
        pool — GQA's K and V pages [P, page, KV, hd], MLA's rope keys
        ("kp", [P, page, rope]) and latents ("vp", [P, page, lora]), the
        paged kernel's two operands in its MLA mode — each with one f32
        scale per page.  SSM and cross entries keep their dense per-slot
        representation."""
        from .. import numerics

        cfg = self.cfg
        if spec.mixer != "attn":
            return self._entry_cache(spec, B, S)
        e = self._entry_cache(spec, B, 0)  # the cross entries, if any
        dt = jnp.uint8 if numerics.kv_quantized(cfg.policy) else cfg.pdtype
        if cfg.attn_impl == "mla":
            kshape = (num_pages, page_size, cfg.qk_rope_dim)
            vshape = (num_pages, page_size, cfg.kv_lora_rank)
        else:
            kshape = vshape = (num_pages, page_size, cfg.n_kv_heads, cfg.hd)
        e["self"] = {
            "kp": jnp.zeros(kshape, dt),
            "vp": jnp.zeros(vshape, dt),
            "ks": jnp.ones((num_pages,), jnp.float32),
            "vs": jnp.ones((num_pages,), jnp.float32),
        }
        return e

    def make_paged_cache(self, B: int, num_pages: int, page_size: int,
                         S: int = 0):
        """Decode cache backed by a ``num_pages``-page pool (page 0 is the
        reserved null page).  Cache memory for attention layers (GQA K/V,
        MLA latents) scales with the pool size, not with slots * max_seq;
        ``S`` only sizes the dense entries (SSM states)."""
        S = S or self.max_seq
        prefix = tuple(
            self._entry_cache_paged(s, B, S, num_pages, page_size)
            for s in self.prefix_specs
        )
        one_block = tuple(
            self._entry_cache_paged(s, B, S, num_pages, page_size)
            for s in self.pattern
        )
        blocks = jax.tree.map(
            lambda a: jnp.repeat(a[None], self.n_blocks, axis=0), one_block
        )
        return {"prefix": prefix, "blocks": blocks}


# --------------------------------------------------------------------------- #
def count_params(cfg, active_only: bool = False, max_seq: int = 1024) -> int:
    """Exact parameter counts from init shapes (no allocation)."""
    import math

    model = Model(cfg, max_seq=max_seq)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = sum(
        math.prod(l.shape) if l.shape else 1
        for l in jax.tree_util.tree_leaves(shapes)
    )
    if not active_only or cfg.n_experts == 0:
        return total
    # subtract the inactive fraction of the routed expert weights held
    E, k = cfg.n_experts, cfg.top_k
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    n_moe_layers = sum(
        1 for i in range(cfg.n_layers) if cfg.is_moe_layer(i)
    )
    routed = n_moe_layers * cfg.held_range[1] * per_expert
    return total - int(routed * (E - k) / E)


def matmul_params(cfg, active_only: bool = True) -> int:
    """Parameters participating in matmuls (for MODEL_FLOPS = 6*N*D).

    Excludes the gather-only embedding table but counts the LM head once
    (tied or untied).
    """
    total = count_params(cfg, active_only=active_only)
    emb = cfg.vocab_padded * cfg.d_model
    if cfg.tie_embeddings:
        return total  # table already single-counted; it backs the LM head
    return total - emb  # drop gather-only embed, keep unembed
