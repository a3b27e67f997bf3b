"""Plain float32 reference of a dense GQA decoder, and the seeded weights
that both the served program and this reference use.

The architecture (Qwen2 / Qwen3 family), as published:

- token embedding; per layer: RMSNorm, attention, residual, RMSNorm,
  SwiGLU MLP, residual; final RMSNorm; LM head (the embedding's
  transpose when ``tie_word_embeddings``);
- RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + w)`` (the scale is stored
  as an offset from 1);
- attention: Q/K/V projections (with bias when ``attention_bias``),
  per-head RMSNorm of q and k when ``qk_norm``, rotary embedding on the
  split halves of each head, causal softmax over ``q.k / sqrt(head_dim)``,
  ``num_key_value_heads`` K/V heads shared by groups of query heads;
- MLP ``(silu(h W_gate) * (h W_up)) W_down``.

This file imports nothing of the program and takes nothing it made.  The
weights are a function of the seed alone (:func:`make_params`), made on
the device in one jitted call in the type they are served in (bfloat16),
in the parameter layout the program consumes; the reference rebuilds any
layer's weights from the seed on its own (:func:`layer_f32`), so it never
holds the whole model in float32.

``precision="f32"`` is the reference (float32 at the highest matmul
precision).  ``precision="control"`` computes in the nearest precisions
below those the configuration states: every matmul operand in FP8 E4M3
(activations with one scale per row, weights with one per tensor) for
the bfloat16 weights and activations, and K/V rounded to int4 (one scale
per page of 16 positions, as the program scales its pages) for the FP8 KV
pages.  The control is the reading a
lower-precision path would give; the benchmark's own runs never compute
it.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# Matrices are drawn with standard deviation 1/sqrt(fan_in), so every
# sublayer adds a unit-scale term to the residual stream and attention
# scores are of order one: attention, the KV pages and the MLP all move the
# logits, as in a trained model.  (At the 0.02 scale of a training init,
# uniform attention over random values is all but invisible in the logits,
# and a broken KV cache would go unseen.)  The embedding and LM head use
# 1/sqrt(hidden_size), which makes the logits of order one.
BIAS_STD = 0.3  # Q/K/V biases
NORM_STD = 0.1  # RMSNorm scale offsets (scale = 1 + w)


def dims(c: dict):
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], hd, c["intermediate_size"],
            c["vocab_size"])


def _layer_specs(c: dict):
    """(path, shape, std) of one layer's leaves, in a fixed order: a
    leaf's position is part of its seed."""
    D, H, KV, hd, F, _ = dims(c)
    d, o, f = D ** -0.5, (H * hd) ** -0.5, F ** -0.5
    specs = [(("ln1",), (D,), NORM_STD),
             (("attn", "wq"), (D, H * hd), d),
             (("attn", "wk"), (D, KV * hd), d),
             (("attn", "wv"), (D, KV * hd), d),
             (("attn", "wo"), (H * hd, D), o)]
    if c.get("attention_bias"):
        specs += [(("attn", "bq"), (H * hd,), BIAS_STD),
                  (("attn", "bk"), (KV * hd,), BIAS_STD),
                  (("attn", "bv"), (KV * hd,), BIAS_STD)]
    if c.get("qk_norm"):
        specs += [(("attn", "q_norm"), (hd,), NORM_STD),
                  (("attn", "k_norm"), (hd,), NORM_STD)]
    specs += [(("ln2",), (D,), NORM_STD),
              (("ffn", "w_gate"), (D, F), d),
              (("ffn", "w_up"), (D, F), d),
              (("ffn", "w_down"), (F, D), f)]
    return specs


def _set(tree: dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _layer(c: dict, key, i):
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    out: Dict = {}
    for j, (path, shape, std) in enumerate(_layer_specs(c)):
        _set(out, path, _normal(jax.random.fold_in(lk, j), shape, std))
    return out


def _top(c: dict, key):
    D, _, _, _, _, V = dims(c)
    out = {"embed": _normal(jax.random.fold_in(key, 2), (V, D), D ** -0.5),
           "final_norm": _normal(jax.random.fold_in(key, 3), (D,), NORM_STD)}
    if not c["tie_word_embeddings"]:
        out["unembed"] = _normal(jax.random.fold_in(key, 4), (D, V),
                                 D ** -0.5)
    return out


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([int(seed), 0x3e1]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(c: dict, seed: int, vocab_rows: int):
    """All weights in bfloat16, in the program's layout, from one jitted
    call.  ``vocab_rows`` is the program's padded vocabulary: rows and
    columns past ``vocab_size`` are zero (the program masks their
    logits)."""
    L = c["num_hidden_layers"]
    V = c["vocab_size"]

    @jax.jit
    def make(key):
        blocks = jax.vmap(lambda i: _layer(c, key, i))(jnp.arange(L))
        top = _top(c, key)
        out = {"blocks": (blocks,), "final_norm": top["final_norm"],
               "embed": jnp.pad(top["embed"], ((0, vocab_rows - V), (0, 0)))}
        if "unembed" in top:
            out["unembed"] = jnp.pad(top["unembed"],
                                     ((0, 0), (0, vocab_rows - V)))
        return out

    return make(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _jitted_layer(frozen: tuple):
    c = dict(frozen)
    return jax.jit(lambda key, i: jax.tree.map(
        lambda a: a.astype(jnp.float32), _layer(c, key, i)))


def layer_f32(c: dict, seed: int, i: int):
    """Layer ``i``'s served (bfloat16) weights, as float32."""
    return _jitted_layer(_freeze(c))(seed_key(seed), jnp.int32(i))


def _freeze(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "attention_bias",
            "qk_norm", "tie_word_embeddings", "rms_norm_eps", "rope_theta",
            "num_hidden_layers")
    return tuple((k, c.get(k)) for k in keys)


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fp8(x, axis):
    """Round to FP8 E4M3 with an absmax scale over ``axis`` (None: one
    scale for the whole tensor); returns float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


PAGE = 16  # tokens per KV page, as the program stores them


def _int4_pages(x):
    """Round K or V [S, KV, hd] to int4 (-7..7) with one absmax scale per
    page of ``PAGE`` positions (all heads), the program's page layout."""
    S = x.shape[0]
    p = x.reshape(S // PAGE, PAGE * x.shape[1] * x.shape[2])
    s = jnp.maximum(jnp.max(jnp.abs(p), axis=-1, keepdims=True), 1e-30) / 7.0
    return (jnp.clip(jnp.round(p / s), -7, 7) * s).reshape(x.shape)


def _mm(a, w, control: bool):
    if control:
        a, w = _fp8(a, -1), _fp8(w, None)
    return a @ w


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, theta):
    S, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _block(c: dict, control: bool, x, p):
    """One decoder layer over one sequence x [S, D] (float32)."""
    D, H, KV, hd, _, _ = dims(c)
    S = x.shape[0]
    eps = c["rms_norm_eps"]
    a = p["attn"]
    h = _norm(x, p["ln1"], eps)
    q, k, v = (_mm(h, a[w], control) for w in ("wq", "wk", "wv"))
    if c.get("attention_bias"):
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = (q.reshape(S, H, hd), k.reshape(S, KV, hd),
               v.reshape(S, KV, hd))
    if c.get("qk_norm"):
        q, k = _norm(q, a["q_norm"], eps), _norm(k, a["k_norm"], eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    if control:
        k, v = _int4_pages(k), _int4_pages(v)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
    x = x + _mm(o.reshape(S, H * hd), a["wo"], control)
    f = p["ffn"]
    h = _norm(x, p["ln2"], eps)
    g = jax.nn.silu(_mm(h, f["w_gate"], control)) * _mm(h, f["w_up"], control)
    return x + _mm(g, f["w_down"], control)


@functools.lru_cache(maxsize=None)
def _jitted_block(frozen: tuple, control: bool):
    c = dict(frozen)
    one = functools.partial(_block, c, control)
    # one sequence at a time: the [heads, S, S] scores of a whole batch
    # would not fit beside a wide layer
    return jax.jit(lambda x, p: jax.lax.map(lambda r: one(r, p), x))


@functools.lru_cache(maxsize=None)
def _jitted_top(frozen: tuple):
    c = dict(frozen)
    return jax.jit(lambda k: jax.tree.map(lambda a: a.astype(jnp.float32),
                                          _top(c, k)))


@functools.lru_cache(maxsize=None)
def _jitted_gaps(frozen: tuple, control: bool):
    """Per row of hidden states: the reference's best logit and its logit
    of a given token; with the control's hidden states as well, the
    reference's logit of the token the control puts first."""
    c = dict(frozen)

    def logits(x, top, ctl):
        x = _norm(x, top["final_norm"], c["rms_norm_eps"])
        w = top.get("unembed")
        w = top["embed"].T if w is None else w
        return _mm(x, w, ctl)

    def run(x, xc, tok, top):
        ref = logits(x, top, False)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        if not control:
            return best, got, got
        first = jnp.argmax(logits(xc, top, True), -1)
        return best, got, jnp.take_along_axis(ref, first[:, None], -1)[:, 0]

    return jax.jit(run)


def top_f32(c: dict, seed: int):
    return _jitted_top(_freeze(c))(seed_key(seed))


def hidden(c: dict, seed: int, seqs: np.ndarray, *, precision: str = "f32"):
    """Hidden states entering the final norm, [n, S, D] float32, of the
    token sequences ``seqs`` [n, S] (causal: padding at the end of a row
    does not touch its earlier positions).  Built layer by layer, each
    layer's weights made from the seed and freed after use."""
    control = {"f32": False, "control": True}[precision]
    frozen = _freeze(c)
    with jax.default_matmul_precision("highest"):
        x = top_f32(c, seed)["embed"][jnp.asarray(seqs, jnp.int32)]
        block = _jitted_block(frozen, control)
        for i in range(c["num_hidden_layers"]):
            x = block(x, layer_f32(c, seed, i))
    return x


ROWS = 128  # LM-head rows per call (fixed, so one shape compiles)


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
        w = top.get("unembed")
        return x @ (top["embed"].T if w is None else w)
