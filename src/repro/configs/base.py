"""Model configuration schema for the architecture zoo.

One frozen dataclass describes every assigned architecture (dense / MoE /
SSM / hybrid / enc-dec / VLM).  Fields unused by a family default to
None/0.  ``smoke()`` derives a reduced same-family config for CPU tests.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import jax.numpy as jnp

from ..numerics.policy import Policy, from_quant_config


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Legacy flat quantization switches — a deprecation shim.

    New code should use a :class:`repro.numerics.Policy` (the ``numerics``
    field of :class:`ModelConfig`, or a named preset via
    ``get_config(..., policy=...)``).  This class survives so old call
    sites and flags keep working: :meth:`to_policy` maps it onto the
    policy tree, and the mapping is pinned bit-identical to the historical
    string-kwarg behavior by ``tests/test_numerics.py``.
    """

    enabled: bool = False
    act_quant: bool = True  # quantize activations (False = weight-only)
    act_fmt: str = "e5m2"   # activations: wide-range format
    weight_fmt: str = "e4m3"  # weights: high-precision format
    mode: str = "rne"       # rounding mode for LNS ops
    # auto: resolved per (shape, backend) by kernels.autotune (XLA on CPU,
    # measured/cached Pallas choice on accelerators) | xla | lns | fused_dequant
    matmul_impl: str = "auto"
    elementwise: bool = False  # route SwiGLU gating/rsqrt through LNS VPU ops
    static_weights: bool = False  # params stored as uint8 codes (inference)
    kv_cache_fp8: bool = False  # KV cache stored as E5M2 codes (decode)
    kv_fmt: str = "e5m2"

    def to_policy(self) -> Policy:
        """The equivalent :class:`repro.numerics.Policy` (cached)."""
        return from_quant_config(self)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling, as a published ``rope_scaling`` of type
    ``yarn`` gives it (DeepSeek-V2): the rotary frequencies blend the
    interpolated (``/ factor``) and the original ones over a ramp between
    the correction dimensions of ``beta_fast`` and ``beta_slow``
    rotations, and the attention's softmax scale gains ``mscale**2``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # attention flavor
    attn_impl: str = "gqa"  # gqa | mla | none
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    rope_theta: float = 10000.0
    yarn: Optional[Yarn] = None  # YaRN rope scaling (MLA's rope lanes)
    # sliding-window pattern: period P with the first (P - n_global) layers
    # local; e.g. gemma2 (2, 1): alternate local/global; gemma3 (6, 1): 5 local
    # then 1 global.  window = local attention span.
    local_global_period: Tuple[int, int] = (1, 1)  # (period, n_global)
    window: int = 0

    # MLA (deepseek)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    moe_period: int = 1   # MoE ffn every `moe_period` layers ...
    moe_offset: int = 0   # ... at indices where i % period == offset
    first_dense: int = 0  # leading layers forced dense
    capacity_factor: float = 1.25  # training dispatch; serving is dropless
    # renormalize the top-k gates to sum to 1 (False: the softmax
    # probabilities of the chosen experts as they are, DeepSeek-V2)
    norm_topk_prob: bool = True
    # [first, first + count) of the routed experts this chip holds (expert
    # parallelism: the router spans all n_experts, the layer computes the
    # held experts' part of the result); count 0 = every expert
    held_experts: Tuple[int, int] = (0, 0)
    # sorted_global: one argsort/scatter over all tokens (simple, but the
    # gather/scatter crosses the data/model sharding -> huge collectives).
    # grouped: route per batch-row (x per seq-shard under SP) so dispatch is
    # shard-local; see EXPERIMENTS.md §Perf hillclimb B.
    moe_dispatch: str = "grouped"

    # SSM (mamba2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    # hybrid pattern: attention at indices where i % attn_period == attn_offset
    attn_period: int = 1
    attn_offset: int = 0

    # enc-dec
    n_enc_layers: int = 0
    enc_context: int = 0  # fixed encoder positions (whisper: 1500)

    # vlm
    n_img_tokens: int = 0  # stub patch-embedding tokens prepended

    # common
    act_fn: str = "silu"  # silu | gelu
    sandwich_norm: bool = False  # gemma2/3 pre+post block norms
    tie_embeddings: bool = False
    emb_scale: bool = False  # gemma scales embeddings by sqrt(d_model)
    norm_eps: float = 1e-6
    pad_vocab_to: int = 2048  # pad embedding table for clean TP
    param_dtype: str = "bfloat16"
    # scan remat policy: "minimal" recomputes whole blocks in backward
    # (lowest memory); "dots" saves matmul outputs (no recompute of the
    # expensive ops, ~2-4x peak memory) — see EXPERIMENTS.md §Perf iter 4.
    remat_policy: str = "minimal"
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    # The numerics policy (repro.numerics.Policy).  None => derived from
    # the legacy ``quant`` shim via QuantConfig.to_policy().
    numerics: Optional[Policy] = None

    # ------------------------------------------------------------------ #
    @property
    def policy(self):
        """The numerics policy model layers consume.

        Returns a :class:`repro.numerics.Policy` — or, when
        ``REPRO_FORCE_LEGACY_QUANTCONFIG=1`` (the deprecation-shim CI
        job), the equivalent :class:`QuantConfig`, which routes the
        layers through the preserved string-kwarg code paths.
        """
        if os.environ.get("REPRO_FORCE_LEGACY_QUANTCONFIG") == "1":
            if self.numerics is not None:
                return self.numerics.to_quant_config()
            return self.quant
        if self.numerics is not None:
            return self.numerics
        return self.quant.to_policy()

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def held_range(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        first, count = self.held_experts
        return (first, count) if count else (0, self.n_experts)

    @property
    def vocab_padded(self) -> int:
        m = self.pad_vocab_to
        return -(-self.vocab // m) * m if m else self.vocab

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def is_attn_layer(self, i: int) -> bool:
        if self.attn_impl == "none":
            return False
        if self.family == "hybrid":
            return i % self.attn_period == self.attn_offset
        return True

    def is_moe_layer(self, i: int) -> bool:
        if self.n_experts == 0:
            return False
        return i >= self.first_dense and i % self.moe_period == self.moe_offset

    def is_global_attn_layer(self, i: int) -> bool:
        period, n_global = self.local_global_period
        return i % period >= period - n_global

    @property
    def layer_pattern_period(self) -> int:
        """Length of the repeating layer pattern (scan super-block size)."""
        import math

        p = self.local_global_period[0]
        if self.family == "hybrid":
            p = max(p, (self.attn_period * self.moe_period)
                    // math.gcd(self.attn_period, self.moe_period) if self.moe_period else self.attn_period)
        elif self.n_experts:
            p = max(p, self.moe_period)
        return p

    def n_params(self) -> int:
        """Total parameter count (embedding included once if tied)."""
        from ..models.model import count_params

        return count_params(self)

    def n_active_params(self) -> int:
        from ..models.model import count_params

        return count_params(self, active_only=True)

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        period = self.layer_pattern_period
        changes = dict(
            name=self.name + "-smoke",
            n_layers=max(2 * period, 2),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) or 2,
            head_dim=16,
            d_ff=128,
            vocab=256,
            pad_vocab_to=64,
            window=min(self.window, 32) if self.window else 0,
        )
        if self.attn_impl == "mla":
            changes.update(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        if self.n_experts:
            E = min(self.n_experts, 8)
            changes.update(n_experts=E, top_k=min(self.top_k, 2), moe_d_ff=64)
            first, count = self.held_experts
            if count:  # the same share of the smaller expert set
                changes.update(held_experts=(first * E // self.n_experts,
                                             max(1, count * E // self.n_experts)))
        if self.ssm_state:
            changes.update(ssm_state=16, ssm_head_dim=16)
        if self.n_enc_layers:
            changes.update(n_enc_layers=2, enc_context=32)
        if self.n_img_tokens:
            changes.update(n_img_tokens=16)
        return dataclasses.replace(self, **changes)
