"""Mixture-of-experts FFN with sorted capacity dispatch.

Token-choice top-k routing; dispatch via argsort-by-expert + static-capacity
scatter (no [T, E, C] one-hot tensor — the buffers are [E, C, D], which
shards cleanly: E over the ``model`` mesh axis for expert parallelism, or
the expert FFN dim over ``model`` when E doesn't divide the axis).

Router runs in f32 (correct top-k under bf16 params), softmax over every
routed expert, greedy top-k; the gates are renormalized over the top k
only when ``cfg.norm_topk_prob``.  Aux losses: Switch load-balance and
router z-loss, returned for the trainer to weigh in.

Training (:func:`moe_ffn`) drops the tokens past an expert's capacity.
Serving's decode and mixed steps (:func:`moe_serve`) are dropless: a
request's output never depends on who shares its batch.  The bucketed
scheduler's prefill (``Model.prefill``) is the exception: it runs
:func:`moe_ffn`, whose dispatch buffers stay at the capacity (a dropless
one would hold [held experts, prompt tokens, D]).  A layer told
that it holds only some routed experts (``cfg.held_experts``, expert
parallelism) routes over all of them and adds only its held experts'
part of the result.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import _act, qlinear


def moe_init(rng, cfg):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    Eh = cfg.held_range[1]
    dt = cfg.pdtype
    ks = jax.random.split(rng, 5)
    scale = 0.02
    p = {
        "router": (jax.random.normal(ks[0], (D, E), jnp.float32) * scale),
        "w_gate": (jax.random.normal(ks[1], (Eh, D, F), jnp.float32) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (Eh, D, F), jnp.float32) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (Eh, F, D), jnp.float32) * scale).astype(dt),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        k2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(k2[0], (D, Fs), jnp.float32) * scale).astype(dt),
            "w_up": (jax.random.normal(k2[1], (D, Fs), jnp.float32) * scale).astype(dt),
            "w_down": (jax.random.normal(k2[2], (Fs, D), jnp.float32) * scale).astype(dt),
        }
    return p


def _expert_mm(buf, w, pol, site=""):
    """[E, C, Din] @ [E, Din, Dout] -> [E, C, Dout], optionally FP8-LNS.

    ``pol`` is a numerics Policy (or the legacy QuantConfig shim).  The
    per-expert matmuls vmap the same policy-resolved path ``qlinear``
    uses, so MoE experts and dense layers share one numerics surface.
    (The preserved QuantConfig branch keeps one historical quirk verbatim:
    it quantized expert activations whenever ``enabled``, ignoring
    ``act_quant`` — the policy path honors the per-site matmul format.)
    """
    from .. import numerics

    if numerics.is_quantized_weight(w):
        from .quantize import resolve_weight

        fmt = numerics.weight_format(pol, site) or "e4m3"
        w = resolve_weight(w, fmt, buf.dtype)
    if pol is not None and numerics.is_legacy_config(pol):
        # preserved QuantConfig string path (REPRO_FORCE_LEGACY_QUANTCONFIG)
        if pol.enabled:
            from .layers import _ste_qmatmul

            return jax.vmap(
                lambda a, b: _ste_qmatmul(a, b, pol.act_fmt, pol.weight_fmt,
                                          pol.matmul_impl)
            )(buf, w).astype(buf.dtype)
        return jnp.einsum("ecd,edf->ecf", buf, w)
    ppol = numerics.as_policy(pol)
    if ppol is not None and ppol.ste_weights:
        return jax.vmap(
            lambda a, b: numerics.matmul(a, b, ppol, site=site)
        )(buf, w).astype(buf.dtype)
    return jnp.einsum("ecd,edf->ecf", buf, w)


def capacity(T: int, k: int, E: int, factor: float) -> int:
    c = int(T * k / E * factor)
    return max(8, -(-c // 8) * 8)  # multiple of 8, at least 8


def _route(p, xf, cfg):
    """Router of tokens xf [T, D]: (logits [T, E] f32, probs, gates [T, k],
    expert ids [T, k])."""
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)
    return logits, probs, gate_vals, ids


def _dispatch(p, xf, gate_vals, ids, cfg, C: int, site: str):
    """Sorted dispatch of tokens xf [T, D] to the held routed experts, ``C``
    slots each (an assignment past its expert's ``C`` is dropped; ``C >=
    T`` drops none, since a token takes an expert at most once).  Returns
    the gate-weighted sum of the held experts' outputs, [T, D] float32."""
    T, D = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    first, n_held = cfg.held_range
    flat_ids = ids.reshape(-1)
    order = jnp.argsort(flat_ids, stable=True)
    tok = order // k
    eid = flat_ids[order]
    starts = jnp.searchsorted(eid, jnp.arange(E))
    rank = jnp.arange(T * k) - starts[eid]
    fits = rank < C
    keep = fits
    if n_held < E:  # absent experts' assignments add nothing here
        keep = fits & (eid >= first) & (eid < first + n_held)
        eid = jnp.clip(eid - first, 0, n_held - 1)
    rank_c = jnp.where(fits, rank, C - 1)

    buf = jnp.zeros((n_held, C, D), xf.dtype).at[eid, rank_c].add(
        xf[tok] * keep[:, None].astype(xf.dtype)
    )
    h = _act(_expert_mm(buf, p["w_gate"], cfg.policy, f"{site}.w_gate"),
             cfg.act_fn)
    h = h * _expert_mm(buf, p["w_up"], cfg.policy, f"{site}.w_up")
    y = _expert_mm(h, p["w_down"], cfg.policy, f"{site}.w_down")

    g_sorted = gate_vals.reshape(-1)[order] * keep
    return jnp.zeros((T, D), jnp.float32).at[tok].add(
        y[eid, rank_c].astype(jnp.float32) * g_sorted[:, None]
    )


def moe_serve(p, x, cfg, *, valid=None, site="blocks.*.ffn"):
    """The served expert layer (decode and mixed steps): x [B, S, D] ->
    (out [B, S, D], counts).

    Dropless: every token of the step is one dispatch group and each held
    expert has a slot for every token, so no assignment is dropped and a
    request's output does not depend on its batch.  The buffers are
    [held experts, B*S, D].  The shared experts are computed in full.
    ``counts`` holds the step's routing assignments of the ``valid`` [B]
    lanes (all lanes when None) to held and to absent experts, int32
    scalars ``moe_held`` and ``moe_absent``."""
    from .layers import gated_mlp

    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    _, _, gate_vals, ids = _route(p, xf, cfg)
    out = _dispatch(p, xf, gate_vals, ids, cfg, B * S, site)
    out = out.reshape(B, S, D).astype(x.dtype)
    if "shared" in p:
        out = out + gated_mlp(x, p["shared"], cfg.policy, cfg.act_fn,
                              site=f"{site}.shared")
    first, n_held = cfg.held_range
    held = (ids >= first) & (ids < first + n_held)
    lane = jnp.ones((B * S, 1), bool) if valid is None else jnp.repeat(
        jnp.asarray(valid, bool), S)[:, None]
    counts = {"moe_held": jnp.sum(held & lane, dtype=jnp.int32),
              "moe_absent": jnp.sum(~held & lane, dtype=jnp.int32)}
    return out, counts


def moe_ffn(p, x, cfg, site="blocks.*.ffn") -> Tuple[jnp.ndarray, dict]:
    """x: [B, S, D] -> (out [B, S, D], aux losses).

    Dispatch strategies (cfg.moe_dispatch):
      * ``grouped`` (default): route per batch-row (further split per
        seq-shard under SP) so the argsort/gather/scatter never crosses a
        sharding boundary — per-layer dispatch collectives drop from
        activation-gather scale (~100 GiB/dev/step on granite) to a single
        act-sized reduce.  Capacity is per-group (slightly tighter drops).
      * ``sorted_global``: one argsort over all B*S tokens (the simple
        textbook formulation; kept as the baseline for EXPERIMENTS.md §Perf
        hillclimb B and for ablation).
    """
    if cfg.moe_dispatch == "grouped":
        from ..parallel.hints import _ctx  # active mesh context, if any

        state = _ctx.get()
        if state is not None:
            return _moe_ffn_shard_map(p, x, cfg, *state, site=site)
        return _moe_ffn_grouped(p, x, cfg, site=site)
    return _moe_ffn_global(p, x, cfg, site=site)


def _moe_ffn_shard_map(p, x, cfg, mesh, hint_specs,
                       site="blocks.*.ffn") -> Tuple[jnp.ndarray, dict]:
    """Shard-local dispatch via shard_map (no SPMD guesswork).

    Tokens stay exactly where the activation sharding puts them; each device
    routes and dispatches ITS tokens locally.  Experts:
      * EP (n_experts % model == 0, e.g. deepseek 64, jamba 16): each model
        rank holds E/tp experts (weights enter the region sharded on dim 0),
        processes the slots routed to its experts, and the partial outputs
        are combined with ONE act-sized psum over `model`.
      * non-EP (granite 40): expert weights enter replicated (the FSDP
        all-gather XLA inserts at the region boundary is the same gather the
        dense path pays) and each device computes its tokens against all
        experts — zero collectives inside the layer.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("model", 1)
    ep = cfg.n_experts % tp == 0 and tp > 1 and hint_specs.get("sp") is None
    act_spec = hint_specs.get("act") or P()
    E = cfg.n_experts

    def w_spec(w):
        """Static-quantized weights are {codes, scale} dicts: shard the
        codes like the weight, replicate the tiny scale."""
        espec = P("model") if ep else P()
        if isinstance(w, dict) and "codes" in w:
            return {"codes": espec, "scale": P()}
        return espec

    wspec = {
        "router": P(),
        "w_gate": w_spec(p["w_gate"]),
        "w_up": w_spec(p["w_up"]),
        "w_down": w_spec(p["w_down"]),
    }

    def region(p_loc, x_loc):
        B_l, S_l, D = x_loc.shape
        xf = x_loc.reshape(-1, D)
        Tg = xf.shape[0]
        k = cfg.top_k
        logits, probs, gate_vals, ids = _route(p_loc, xf, cfg)
        aux = _aux_losses(logits, probs, ids, cfg)
        for ax in mesh.axis_names:
            aux = {kk: jax.lax.pmean(vv, ax) for kk, vv in aux.items()}

        flat_ids = ids.reshape(-1)
        order = jnp.argsort(flat_ids, stable=True)
        tok = order // k
        eid = flat_ids[order]
        starts = jnp.searchsorted(eid, jnp.arange(E))
        rank = jnp.arange(Tg * k) - starts[eid]

        if ep:
            e_loc = E // tp
            off = jax.lax.axis_index("model") * e_loc
            local_eid = jnp.clip(eid - off, 0, e_loc - 1)
            mine = (eid >= off) & (eid < off + e_loc)
        else:
            e_loc = E
            local_eid = eid
            mine = jnp.ones_like(eid, bool)

        C = capacity(Tg, k, E, cfg.capacity_factor)
        keep = (rank < C) & mine
        rank_c = jnp.where(rank < C, rank, C - 1)

        buf = jnp.zeros((e_loc, C, D), x_loc.dtype).at[local_eid, rank_c].add(
            xf[tok] * keep[:, None].astype(x_loc.dtype)
        )
        h = _act(_expert_mm(buf, p_loc["w_gate"], cfg.policy,
                            f"{site}.w_gate"), cfg.act_fn)
        h = h * _expert_mm(buf, p_loc["w_up"], cfg.policy, f"{site}.w_up")
        y = _expert_mm(h, p_loc["w_down"], cfg.policy, f"{site}.w_down")

        g_sorted = gate_vals.reshape(-1)[order] * keep
        out = jnp.zeros((Tg, D), jnp.float32).at[tok].add(
            y[local_eid, rank_c].astype(jnp.float32) * g_sorted[:, None]
        )
        if ep:
            out = jax.lax.psum(out, "model")
        return out.reshape(B_l, S_l, D).astype(x_loc.dtype), aux

    p_in = {k_: p[k_] for k_ in wspec}
    out, aux = jax.shard_map(
        region,
        mesh=mesh,
        in_specs=(wspec, act_spec),
        out_specs=(act_spec, P()),
        check_vma=False,
    )(p_in, x)

    if "shared" in p:
        from .layers import gated_mlp

        out = out + gated_mlp(x, p["shared"], cfg.policy, cfg.act_fn,
                              site=f"{site}.shared")
    return out, aux


def _moe_ffn_grouped(p, x, cfg, site="blocks.*.ffn") -> Tuple[jnp.ndarray, dict]:
    from ..parallel.hints import hint_meta

    B, S, D = x.shape
    sp = hint_meta("sp") or 1
    g2 = sp if (sp > 1 and S % sp == 0) else 1
    xg = x.reshape(B * g2, S // g2, D)

    def one_group(xr):  # [Tg, D]
        return _dispatch_group(p, xr, cfg, site=site)

    out, aux = jax.vmap(one_group)(xg)
    out = out.reshape(B, S, D)
    aux = {k_: jnp.mean(v) for k_, v in aux.items()}

    if "shared" in p:
        from .layers import gated_mlp

        out = out + gated_mlp(x, p["shared"], cfg.policy, cfg.act_fn,
                              site=f"{site}.shared")
    return out, aux


def _aux_losses(logits, probs, ids, cfg) -> dict:
    """Switch load-balance and router z-loss of one routed group."""
    E, k = cfg.n_experts, cfg.top_k
    me = probs.mean(0)  # mean router prob per expert
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    ce = counts / (ids.shape[0] * k)  # fraction of routed slots per expert
    return {"moe_lb": E * jnp.sum(me * ce),
            "moe_z": jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)}


def _dispatch_group(p, xf, cfg, site="blocks.*.ffn") -> Tuple[jnp.ndarray, dict]:
    """Sorted-capacity dispatch over one token group [Tg, D] (local)."""
    Tg, D = xf.shape
    logits, probs, gate_vals, ids = _route(p, xf, cfg)
    aux = _aux_losses(logits, probs, ids, cfg)
    C = capacity(Tg, cfg.top_k, cfg.n_experts, cfg.capacity_factor)
    out = _dispatch(p, xf, gate_vals, ids, cfg, C, site)
    return out.astype(xf.dtype), aux


def _moe_ffn_global(p, x, cfg, site="blocks.*.ffn") -> Tuple[jnp.ndarray, dict]:
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)

    logits, probs, gate_vals, ids = _route(p, xf, cfg)
    aux = _aux_losses(logits, probs, ids, cfg)
    C = capacity(T, cfg.top_k, cfg.n_experts, cfg.capacity_factor)
    out = _dispatch(p, xf, gate_vals, ids, cfg, C, site).astype(x.dtype)

    if "shared" in p:
        from .layers import gated_mlp

        out = out + gated_mlp(x, p["shared"], cfg.policy, cfg.act_fn,
                              site=f"{site}.shared").reshape(T, D)

    return out.reshape(B, S, D), aux
