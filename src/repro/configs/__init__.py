"""Architecture registry: ``get_config(arch_id)`` + shape definitions."""
from __future__ import annotations

import dataclasses
from typing import Dict

from .base import ModelConfig, QuantConfig

from .qwen2_0_5b import CONFIG as _qwen2
from .gemma2_27b import CONFIG as _gemma2
from .qwen3_14b import CONFIG as _qwen3
from .gemma3_12b import CONFIG as _gemma3
from .whisper_base import CONFIG as _whisper
from .jamba_v01_52b import CONFIG as _jamba
from .deepseek_v2_lite_16b import CONFIG as _dsv2
from .deepseek_v2_lite_ep4 import CONFIG as _dsv2_ep4
from .granite_moe_3b import CONFIG as _granite
from .llava_next_mistral_7b import CONFIG as _llava
from .mamba2_780m import CONFIG as _mamba2

CONFIGS: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        _qwen2, _gemma2, _qwen3, _gemma3, _whisper,
        _jamba, _dsv2, _granite, _llava, _mamba2, _dsv2_ep4,
    ]
}

# The assigned input-shape set (seq_len, global_batch, kind).
SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}

# long_500k requires sub-quadratic attention state: run for SSM/hybrid and
# the sliding-window-dominant gemmas; skip for pure full-attention archs.
LONG_CONTEXT_ARCHS = {"mamba2-780m", "jamba-v0.1-52b", "gemma2-27b", "gemma3-12b"}


def shape_supported(arch: str, shape: str) -> tuple[bool, str]:
    """(supported, reason-if-not) for an (arch, shape) cell."""
    if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, "pure full-attention arch: 500k KV decode excluded (DESIGN.md §6)"
    return True, ""


def legacy_quant_config(quant: str) -> QuantConfig:
    """The historical ``--quant`` flag values as QuantConfig (deprecated:
    these map through ``QuantConfig.to_policy()``; prefer the named
    presets in :data:`repro.numerics.LEGACY_QUANT_PRESETS`)."""
    if quant == "none":
        return QuantConfig()
    if quant == "fp8_w8":  # static weight-only FP8 (inference)
        return QuantConfig(enabled=False, static_weights=True)
    if quant == "fp8_w8kv8":  # weights + KV cache in FP8 (serving)
        return QuantConfig(enabled=False, static_weights=True, kv_cache_fp8=True)
    if quant == "fp8_w8_train":  # weight-only quantized training
        return QuantConfig(enabled=True, act_quant=False)
    impl = {"fp8_lns": "xla", "fp8_lns_pallas": "lns"}[quant]
    return QuantConfig(enabled=True, matmul_impl=impl)


def get_config(arch: str, *, quant: str = "none", smoke: bool = False,
               policy=None) -> ModelConfig:
    """Config lookup + numerics selection.

    ``policy``: a :class:`repro.numerics.Policy`, a registered preset name
    (``serve_fp8_paged``, ``train_fp8``, ...), or None.  ``quant`` is the
    deprecated flat flag — it still works, mapping through
    ``QuantConfig.to_policy()`` — but passing both is an error.
    """
    cfg = CONFIGS[arch]
    if smoke:
        cfg = cfg.smoke()
    if policy is not None:
        if quant != "none":
            raise ValueError(
                f"pass either policy={policy!r} or the deprecated "
                f"quant={quant!r}, not both"
            )
        from ..numerics import get_policy

        pol = get_policy(policy)
        # mirror into the legacy shim so REPRO_FORCE_LEGACY_QUANTCONFIG
        # runs see an equivalent QuantConfig
        return dataclasses.replace(cfg, numerics=pol,
                                   quant=pol.to_quant_config())
    if quant != "none":
        cfg = dataclasses.replace(cfg, quant=legacy_quant_config(quant))
    return cfg
