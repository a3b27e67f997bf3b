"""Operations and bytes the served work needs, from its shapes.

These are the algorithm's needs, not what the program happens to move:
a roofline share or ``mfu`` built on them says how far the measured time
is from the least the chip could take.  Every count is per step of the
continuous scheduler, reckoned from the context lengths the step ran.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KV_CODE_BYTES = 1  # E5M2 page codes
PAGE_SCALE_BYTES = 4  # one f32 scale per page, for K and for V


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=hd,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    def layer_matmul_params(self) -> int:
        D, H, KV, hd, F = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def paged_kernel_cost(ctx: np.ndarray, d: Dims, page: int):
    """(bytes, ops) one fused write+attend call of one layer needs, for the
    active lanes whose context lengths (tokens already cached, before this
    token) are ``ctx``.  Each lane reads the K and V codes and scales of
    the pages covering ``ctx + 1`` tokens (the new row rides in with the
    call), its quantized query, writes the new K/V row and its [heads,
    head_dim] f32 output.  Ops: q.k and p.v, two per multiply-add, over
    ``ctx + 1`` positions."""
    ctx = np.asarray(ctx, np.int64)
    n = ctx + 1
    pages = -(-n // page)
    row = d.kv_heads * d.head_dim
    kv_bytes = 2 * pages * (page * row * KV_CODE_BYTES + PAGE_SCALE_BYTES)
    per_lane = (kv_bytes + d.heads * d.head_dim  # q codes
                + 2 * row * KV_CODE_BYTES         # new K/V row
                + d.heads * d.head_dim * 4)       # f32 output
    ops = 4 * d.heads * d.head_dim * n
    return int(per_lane.sum()), int(ops.sum())


def step_cost(lengths: np.ndarray, n_new: np.ndarray, emitting: int,
              d: Dims, page: int):
    """Needs of one scheduler step (``Model.step_paged``): lane ``b`` feeds
    ``n_new[b]`` tokens at positions ``lengths[b] + t``.  Returns a dict of
    the paged kernel's bytes and ops over every layer and sub-step, the
    valid tokens processed, and their useful FLOPs: 2 per matmul parameter
    per token, attention over each token's context, and the LM head once
    for each of the ``emitting`` lanes that sample a token."""
    lengths = np.asarray(lengths, np.int64)
    n_new = np.asarray(n_new, np.int64)
    T = int(n_new.max()) if n_new.size else 0
    kb = ko = 0
    ctx_all = []
    for t in range(T):
        act = n_new > t
        ctx = lengths[act] + t
        b, o = paged_kernel_cost(ctx, d, page)
        kb += b * d.layers
        ko += o * d.layers
        ctx_all.append(ctx)
    ctx = np.concatenate(ctx_all) if ctx_all else np.zeros(0, np.int64)
    tokens = int(ctx.size)
    attn = 4 * d.heads * d.head_dim * int((ctx + 1).sum()) * d.layers
    flops = (2 * d.layer_matmul_params() * d.layers * tokens + attn
             + 2 * d.d_model * d.vocab * emitting)
    return {"kernel_bytes": kb, "kernel_ops": ko, "tokens": tokens,
            "flops": flops}
