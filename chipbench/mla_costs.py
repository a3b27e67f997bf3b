"""Operations and bytes the paged kernel's MLA mode needs, from shapes.

In its MLA mode the fused write+attend kernel scores one query of
``[q_pe ‖ q_eff]`` per head against each position's rope key (``kpe``)
and latent (``ckv``), and sums the latents weighted by the softmax: one
KV head, every query head in its group.  As in ``costs.py`` these are the
algorithm's needs, per step of the continuous scheduler, reckoned from
the context lengths the step ran.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .costs import KV_CODE_BYTES, PAGE_SCALE_BYTES


@dataclasses.dataclass(frozen=True)
class MlaDims:
    layers: int   # MLA layers (every layer)
    heads: int
    latent: int   # kv_lora_rank
    rope: int     # qk_rope_head_dim

    @classmethod
    def from_config(cls, c: dict) -> "MlaDims":
        return cls(layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   latent=c["kv_lora_rank"], rope=c["qk_rope_head_dim"])


def kernel_cost(ctx: np.ndarray, d: MlaDims, page: int):
    """(bytes, ops) one fused write+attend call of one MLA layer needs,
    for the active lanes whose context lengths (tokens already cached,
    before this token) are ``ctx``.  Each lane reads the latent and rope
    key codes of the pages covering ``ctx + 1`` tokens (576 B a row at
    DeepSeek-V2's widths) and both pages' scales, its quantized query
    ``[q_pe ‖ q_eff]``, writes the new row and its [heads, latent] f32
    output.  Ops, two per multiply-add, over ``ctx + 1`` positions: the
    scores over ``rope + latent`` lanes and the latent sum."""
    ctx = np.asarray(ctx, np.int64)
    n = ctx + 1
    pages = -(-n // page)
    row = d.latent + d.rope
    page_bytes = page * row * KV_CODE_BYTES + 2 * PAGE_SCALE_BYTES
    per_lane = (pages * page_bytes
                + d.heads * row          # q codes
                + row * KV_CODE_BYTES    # the new row
                + d.heads * d.latent * 4)  # f32 output
    ops = (2 * d.heads * row + 2 * d.heads * d.latent) * n
    return int(per_lane.sum()), int(ops.sum())


def step_cost(lengths: np.ndarray, n_new: np.ndarray, d: MlaDims,
              page: int):
    """The kernel's (bytes, ops) over every layer and sub-step of one
    scheduler step: lane ``b`` feeds ``n_new[b]`` tokens at positions
    ``lengths[b] + t``."""
    lengths = np.asarray(lengths, np.int64)
    n_new = np.asarray(n_new, np.int64)
    T = int(n_new.max()) if n_new.size else 0
    kb = ko = 0
    for t in range(T):
        b, o = kernel_cost(lengths[n_new > t] + t, d, page)
        kb += b * d.layers
        ko += o * d.layers
    return kb, ko


def traced_cost(run):
    """Summed (bytes, ops) of the kernel over the traced steps."""
    d = MlaDims.from_config(run.cell.model)
    kb = ko = 0
    for s in run.traced_steps():
        if s.T:
            b, o = step_cost(s.lengths, s.n_new, d, run.page_size)
            kb += b
            ko += o
    return kb, ko
