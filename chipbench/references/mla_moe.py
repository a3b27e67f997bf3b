"""Plain float32 reference of a DeepSeek-V2 decoder (latent attention,
routed and shared experts, leading dense layers) at one chip's share of
its routed experts, and the seeded weights that both the served program
and this reference use.

The architecture, as published (DeepSeek-V2, arXiv:2405.04434, and the
``deepseek_v2`` modelling code of its Hugging Face release):

- token embedding; per layer: RMSNorm, MLA attention, residual, RMSNorm,
  feed-forward, residual; final RMSNorm; untied LM head;
- RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + w)`` (the scale is stored
  as an offset from 1);
- MLA without a query low-rank (``q_lora_rank`` null): ``q = h W_q``,
  split per head into ``q_nope`` (``qk_nope_head_dim``) and ``q_pe``
  (``qk_rope_head_dim``); ``[c_kv ‖ k_pe] = h W_dkv``, ``c_kv`` through
  its own RMSNorm (``kv_lora_rank`` wide); keys ``[c_kv W_uk ‖ k_pe]``
  (``k_pe`` shared by every head), values ``c_kv W_uv``; causal softmax
  over ``q.k`` times ``(nope + rope) ** -0.5 * mscale**2``; ``W_o``;
- rotary embedding of ``q_pe`` and ``k_pe`` on their split halves, with
  YaRN frequencies (``rope_scaling``): the extrapolated frequencies
  ``theta^(-2i/d)`` and the interpolated ones (divided by ``factor``)
  blend over a linear ramp between the dimensions at which
  ``original_max_position_embeddings`` makes ``beta_fast`` and
  ``beta_slow`` rotations; cos and sin are scaled by
  ``m(mscale) / m(mscale_all_dim)`` and the softmax by
  ``m(mscale_all_dim)**2``, where ``m(s) = 0.1 s ln(factor) + 1``;
- the first ``first_k_dense_replace`` layers: a SwiGLU MLP of
  ``intermediate_size``; every later layer: ``n_shared_experts`` SwiGLU
  experts of ``moe_intermediate_size`` (one MLP of the summed width),
  plus the routed experts: softmax over all routed experts, greedy top
  ``num_experts_per_tok``, gates the chosen probabilities as they are
  (``norm_topk_prob`` false), scaled by ``routed_scaling_factor``.

One chip's share (``held_experts``): the router keeps its published width
(``router_width`` outputs) and its top-k; the layer adds only the part of
the routed result that its experts ``first .. first + n_routed_experts -
1`` give (each computed for every token and weighted by its gate, which
is 0 where the token did not choose it).  The shared experts and the
dense layers are computed whole.

Departure, moot for random weights: the published code permutes each
rope head's lanes from interleaved pairs to halves before rotating (a
fixed permutation of ``W_q``'s and ``W_dkv``'s rope columns); this
reference, like the served program, rotates the split halves directly.

This file imports nothing of the program and takes nothing it made.  The
weights are a function of the seed alone (:func:`make_params`), made on
the device in one jitted call in the type they are served in (bfloat16;
the router in float32, as the program keeps it), in the program's
parameter layout; the reference rebuilds any layer's weights from the
seed on its own (:func:`layer_f32`), so it never holds the whole model in
float32.

``precision="f32"`` is the reference (float32 at the highest matmul
precision).  ``precision="control"`` computes in the nearest precisions
below those the configuration states: every matmul operand in FP8 E4M3
(activations with one scale per row, weights with one per matrix) for
the bfloat16 weights and activations; for the FP8 operands of the
attention scores, the latents and rope keys rounded to int4 with one
scale per page of 16 positions (as the program scales its pages) and
the query rounded to int4 with one scale per position (as the program
scales its E5M2 query, one scale per slot across the heads).
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# Matrices are drawn with standard deviation 1/sqrt(fan_in), so every
# sublayer adds a unit-scale term to the residual stream and attention
# scores are of order one (see dense_gqa.py); the router too, so that the
# expert choice is spread and moves with the input.
NORM_STD = 0.1  # RMSNorm scale offsets (scale = 1 + w)
PAGE = 16  # tokens per latent page, as the program stores them


def dims(c: dict) -> dict:
    share = c["held_experts"]
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], L=c["kv_lora_rank"], F=c["intermediate_size"],
        Fm=c["moe_intermediate_size"],
        Fs=c["n_shared_experts"] * c["moe_intermediate_size"],
        E=share["router_width"], first=share["first"],
        Eh=c["n_routed_experts"], k=c["num_experts_per_tok"],
        V=c["vocab_size"], dense=c["first_k_dense_replace"])


def _layer_specs(c: dict, dense: bool):
    """(path, shape, std, dtype) of one layer's leaves, in a fixed order: a
    leaf's position is part of its seed."""
    d = dims(c)
    D, H, L = d["D"], d["H"], d["L"]
    specs = [(("ln1",), (D,), NORM_STD),
             (("attn", "wq"), (D, H * (d["dn"] + d["dr"])), D ** -0.5),
             (("attn", "w_dkv"), (D, L + d["dr"]), D ** -0.5),
             (("attn", "kv_norm"), (L,), NORM_STD),
             (("attn", "w_uk"), (L, H * d["dn"]), L ** -0.5),
             (("attn", "w_uv"), (L, H * d["dv"]), L ** -0.5),
             (("attn", "wo"), (H * d["dv"], D), (H * d["dv"]) ** -0.5),
             (("ln2",), (D,), NORM_STD)]
    if dense:
        F = d["F"]
        specs += [(("ffn", "w_gate"), (D, F), D ** -0.5),
                  (("ffn", "w_up"), (D, F), D ** -0.5),
                  (("ffn", "w_down"), (F, D), F ** -0.5)]
    else:
        Eh, Fm, Fs = d["Eh"], d["Fm"], d["Fs"]
        specs += [(("ffn", "router"), (D, d["E"]), D ** -0.5),
                  (("ffn", "w_gate"), (Eh, D, Fm), D ** -0.5),
                  (("ffn", "w_up"), (Eh, D, Fm), D ** -0.5),
                  (("ffn", "w_down"), (Eh, Fm, D), Fm ** -0.5),
                  (("ffn", "shared", "w_gate"), (D, Fs), D ** -0.5),
                  (("ffn", "shared", "w_up"), (D, Fs), D ** -0.5),
                  (("ffn", "shared", "w_down"), (Fs, D), Fs ** -0.5)]
    return specs


def _set(tree: dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _layer(c: dict, key, i, dense: bool):
    """Layer ``i``'s weights as served: bfloat16, the router float32."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    out: Dict = {}
    for j, (path, shape, std) in enumerate(_layer_specs(c, dense)):
        w = _normal(jax.random.fold_in(lk, j), shape, std)
        _set(out, path, w.astype(jnp.float32) if path[-1] == "router" else w)
    return out


def _top(c: dict, key):
    d = dims(c)
    D, V = d["D"], d["V"]
    return {"embed": _normal(jax.random.fold_in(key, 2), (V, D), D ** -0.5),
            "final_norm": _normal(jax.random.fold_in(key, 3), (D,), NORM_STD),
            "unembed": _normal(jax.random.fold_in(key, 4), (D, V),
                               D ** -0.5)}


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([int(seed), 0x3e1]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(c: dict, seed: int, vocab_rows: int):
    """All weights, in the program's layout, from one jitted call: the
    dense layers as ``prefix``, the expert layers stacked as ``blocks``
    (made one layer at a time, so no layer's float32 draw outlives it).
    ``vocab_rows`` is the program's padded vocabulary: rows and columns
    past ``vocab_size`` are zero (the program masks their logits)."""
    d = dims(c)
    n, L = d["dense"], c["num_hidden_layers"]
    V = d["V"]

    @jax.jit
    def make(key):
        prefix = tuple(_layer(c, key, i, True) for i in range(n))
        blocks = jax.lax.map(lambda i: _layer(c, key, i, False),
                             jnp.arange(n, L))
        top = _top(c, key)
        return {"prefix": prefix, "blocks": (blocks,),
                "final_norm": top["final_norm"],
                "embed": jnp.pad(top["embed"], ((0, vocab_rows - V), (0, 0))),
                "unembed": jnp.pad(top["unembed"],
                                   ((0, 0), (0, vocab_rows - V)))}

    return make(seed_key(seed))


def _freeze(c: dict) -> str:
    return json.dumps(c, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(frozen: str, dense: bool):
    c = json.loads(frozen)
    return jax.jit(lambda key, i: jax.tree.map(
        lambda a: a.astype(jnp.float32), _layer(c, key, i, dense)))


def layer_f32(c: dict, seed: int, i: int):
    """Layer ``i``'s served weights, as float32."""
    dense = i < dims(c)["dense"]
    return _jitted_layer(_freeze(c), dense)(seed_key(seed), jnp.int32(i))


@functools.lru_cache(maxsize=None)
def _jitted_top(frozen: str):
    c = json.loads(frozen)
    return jax.jit(lambda k: jax.tree.map(lambda a: a.astype(jnp.float32),
                                          _top(c, k)))


def top_f32(c: dict, seed: int):
    return _jitted_top(_freeze(c))(seed_key(seed))


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fp8(x, axis):
    """Round to FP8 E4M3 with an absmax scale over ``axis`` (None: one
    scale for the whole tensor); returns float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int4(x):
    """Round the rows of x [n, w] to int4 (-7..7), one absmax scale each."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 7.0
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _int4_pages(x):
    """Round a latent or rope-key sequence [S, d] to int4 with one scale
    per page of ``PAGE`` positions."""
    S, w = x.shape
    return _int4(x.reshape(S // PAGE, PAGE * w)).reshape(x.shape)


def _mm(a, w, control: bool):
    if control:
        a, w = _fp8(a, -1), _fp8(w, None)
    return a @ w


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + w)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(c: dict):
    """(inverse frequencies [rope/2], factor on cos and sin, softmax
    scale) of the published ``rope_scaling``."""
    dim, theta = c["qk_rope_head_dim"], c["rope_theta"]
    y = c["rope_scaling"]
    f = y["factor"]
    half = dim // 2

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    mask = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    freqs = extra / f * (1.0 - mask) + extra * mask
    amp = _mscale(f, y["mscale"]) / _mscale(f, y["mscale_all_dim"])
    m = _mscale(f, y["mscale_all_dim"])
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return freqs.astype(np.float32), amp, scale


def _rope(x, freqs, amp):
    """x [S, heads, d]: rotate the split halves of each head."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None] * amp, jnp.sin(ang)[:, None] * amp
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attn(c: dict, control: bool, h, a):
    d = dims(c)
    S = h.shape[0]
    H, dn, dr, dv, L = d["H"], d["dn"], d["dr"], d["dv"], d["L"]
    freqs, amp, scale = yarn(c)
    q = _mm(h, a["wq"], control).reshape(S, H, dn + dr)
    dkv = _mm(h, a["w_dkv"], control)
    ckv = _norm(dkv[:, :L], a["kv_norm"], c["rms_norm_eps"])
    q_pe = _rope(q[..., dn:], freqs, amp)
    kpe = _rope(dkv[:, None, L:], freqs, amp)[:, 0]
    if control:
        ckv, kpe = _int4_pages(ckv), _int4_pages(kpe)
    k = jnp.concatenate([_mm(ckv, a["w_uk"], control).reshape(S, H, dn),
                         jnp.broadcast_to(kpe[:, None], (S, H, dr))], -1)
    v = _mm(ckv, a["w_uv"], control).reshape(S, H, dv)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    if control:  # one scale per position, across the heads
        q = _int4(q.reshape(S, H * (dn + dr))).reshape(q.shape)
    s = jnp.einsum("shd,thd->hst", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
    return _mm(o.reshape(S, H * dv), a["wo"], control)


def _mlp(h, f, control: bool):
    g = jax.nn.silu(_mm(h, f["w_gate"], control)) * _mm(h, f["w_up"], control)
    return _mm(g, f["w_down"], control)


def _experts(c: dict, control: bool, h, f):
    """The held routed experts' part of the layer [S, D], and the shared
    experts'."""
    d = dims(c)
    probs = jax.nn.softmax(_mm(h, f["router"], control), -1)
    gates, ids = jax.lax.top_k(probs, d["k"])
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * c["routed_scaling_factor"]
    held = d["first"] + jnp.arange(d["Eh"])
    w = jnp.sum(gates[:, :, None] * (ids[:, :, None] == held), 1)  # [S, Eh]
    wg, wu, wd = f["w_gate"], f["w_up"], f["w_down"]
    if control:  # one scale per expert matrix
        hq = _fp8(h, -1)
        wg, wu, wd = (_fp8(x, (1, 2)) for x in (wg, wu, wd))
    else:
        hq = h
    g = jax.nn.silu(jnp.einsum("sd,edf->esf", hq, wg)) * jnp.einsum(
        "sd,edf->esf", hq, wu)
    if control:
        g = _fp8(g, -1)
    y = jnp.einsum("esf,efd->esd", g, wd)
    return jnp.einsum("se,esd->sd", w, y) + _mlp(h, f["shared"], control)


def _block(c: dict, control: bool, dense: bool, x, p):
    """One decoder layer over one sequence x [S, D] (float32)."""
    eps = c["rms_norm_eps"]
    x = x + _attn(c, control, _norm(x, p["ln1"], eps), p["attn"])
    h = _norm(x, p["ln2"], eps)
    return x + (_mlp(h, p["ffn"], control) if dense
                else _experts(c, control, h, p["ffn"]))


@functools.lru_cache(maxsize=None)
def _jitted_block(frozen: str, control: bool, dense: bool):
    c = json.loads(frozen)
    one = functools.partial(_block, c, control, dense)
    # one sequence at a time: the [heads, S, S] scores and the held
    # experts' [experts, S, width] activations of a whole batch would not
    # fit beside a layer
    return jax.jit(lambda x, p: jax.lax.map(lambda r: one(r, p), x))


def hidden(c: dict, seed: int, seqs: np.ndarray, *, precision: str = "f32"):
    """Hidden states entering the final norm, [n, S, D] float32, of the
    token sequences ``seqs`` [n, S] (causal: padding at the end of a row
    does not touch its earlier positions).  Built layer by layer, each
    layer's weights made from the seed and freed after use."""
    control = {"f32": False, "control": True}[precision]
    frozen = _freeze(c)
    n_dense = dims(c)["dense"]
    with jax.default_matmul_precision("highest"):
        x = top_f32(c, seed)["embed"][jnp.asarray(seqs, jnp.int32)]
        for i in range(c["num_hidden_layers"]):
            block = _jitted_block(frozen, control, i < n_dense)
            x = block(x, layer_f32(c, seed, i))
    return x


ROWS = 128  # LM-head rows per call (fixed, so one shape compiles)


@functools.lru_cache(maxsize=None)
def _jitted_gaps(frozen: str, control: bool):
    """Per row of hidden states: the reference's best logit and its logit
    of a given token; with the control's hidden states as well, the
    reference's logit of the token the control puts first."""
    c = json.loads(frozen)

    def logits(x, top, ctl):
        x = _norm(x, top["final_norm"], c["rms_norm_eps"])
        return _mm(x, top["unembed"], ctl)

    def run(x, xc, tok, top):
        ref = logits(x, top, False)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        if not control:
            return best, got, got
        first = jnp.argmax(logits(xc, top, True), -1)
        return best, got, jnp.take_along_axis(ref, first[:, None], -1)[:, 0]

    return jax.jit(run)


def served_gaps(c: dict, seed: int, items, *, pad_to: int,
                control: bool = False):
    """``items``: (prompt, served tokens) pairs.  For each served token
    (predicted after the prompt and the tokens served before it) the gap
    by which the reference's logit of it lies below the reference's best;
    with ``control``, also the gap of the token the control computation
    puts first at the same position.  Returns one (gaps, control gaps or
    None) pair of numpy arrays per item."""
    seqs = np.zeros((len(items), pad_to), np.int64)
    for n, (prompt, served) in enumerate(items):
        seq = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(served[:-1], np.int64)])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} exceeds {pad_to}")
        seqs[n, :len(seq)] = seq
    x = hidden(c, seed, seqs)
    xc = hidden(c, seed, seqs, precision="control") if control else x
    top = top_f32(c, seed)
    fn = _jitted_gaps(_freeze(c), control)
    out = []
    with jax.default_matmul_precision("highest"):
        for n, (prompt, served) in enumerate(items):
            pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
            tok = np.asarray(served, np.int32)
            g, cg = [], []
            for a in range(0, len(pos), ROWS):
                p = np.zeros(ROWS, np.int64)
                t = np.zeros(ROWS, np.int32)
                k = min(ROWS, len(pos) - a)
                p[:k], t[:k] = pos[a:a + k], tok[a:a + k]
                best, got, first = fn(x[n][p], xc[n][p], jnp.asarray(t), top)
                g.append(np.asarray(best - got)[:k])
                cg.append(np.asarray(best - first)[:k])
            out.append((np.concatenate(g),
                        np.concatenate(cg) if control else None))
    return out


def logits(c: dict, seed: int, tokens: np.ndarray):
    """Reference logits [S, vocab] of one token sequence (small sizes)."""
    x = hidden(c, seed, np.asarray(tokens)[None])[0]
    top = top_f32(c, seed)
    with jax.default_matmul_precision("highest"):
        x = _norm(x, top["final_norm"], c["rms_norm_eps"])
        return x @ top["unembed"]
