"""DeepSeek-V2-Lite as one chip of a v5e-4 host serves it: the 64 routed
experts of every MoE layer are 4-way expert-parallel, and this chip holds
experts 0-15; attention, the dense first layer, the shared experts, the
embedding and the head are replicated (attention data-parallel: each chip
serves its own slots with all 16 heads).  Every layer and the whole
vocabulary are kept.
"""
import dataclasses

from .deepseek_v2_lite_16b import CONFIG as _FULL

CONFIG = dataclasses.replace(_FULL, name="deepseek-v2-lite-ep4",
                             held_experts=(0, 16))
