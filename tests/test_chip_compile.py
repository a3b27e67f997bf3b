"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes the chip refuses, primitives with no Mosaic lowering, kernels
that do not fit VMEM.  Every kernel runs at qwen2-0.5b widths (14 heads, 2
KV heads, head_dim 64, d_model 896, d_ff 4864) and must come out as a
``tpu_custom_call``.  Nothing here runs; a compile that passes is not a
chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as pa
from repro.kernels.fp8_elementwise import _fp8_elementwise
from repro.kernels.lns_matmul import _lns_matmul

KV, G, HD, PAGE = 2, 7, 64, 16  # qwen2-0.5b attention geometry, 16-token pages
SLOTS, MAXP = 8, 32             # 8 slots of up to 512 tokens
POOL = 65536                    # pages: a deployment-sized pool


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler for this platform
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _page_args():
    return [((SLOTS, 1, KV * G, HD), jnp.bfloat16),
            ((POOL, PAGE, KV, HD), jnp.uint8), ((POOL, PAGE, KV, HD), jnp.uint8),
            ((POOL,), jnp.float32), ((POOL,), jnp.float32),
            ((SLOTS, MAXP), jnp.int32), ((SLOTS,), jnp.int32)]


@pytest.mark.parametrize("ppb,spb", [(1, 1), (2, 1), (4, 2), (1, 4)])
def test_paged_kernel_compiles(one_chip, ppb, spb):
    def unfused(q, kp, vp, ks, vs, bt, ln):
        return pa._paged_decode_attention(
            q, kp, vp, ks, vs, bt, ln, fmt="e5m2", n_kv_heads=KV, mode="rne",
            window=0, cap=0.0, impl="kernel", interpret=False, ppb=ppb,
            spb=spb)

    assert "tpu_custom_call" in _compile_text(unfused, one_chip, *_page_args())


@pytest.mark.parametrize("ppb,spb", [(1, 1), (2, 2)])
def test_fused_write_attend_kernel_compiles(one_chip, ppb, spb):
    def fused(q, kp, vp, ks, vs, bt, ln, k_new, v_new, keys, mask):
        return pa._fused_decode_write_attend(
            q, k_new, v_new, kp, vp, ks, vs, bt, ln, keys, keys, mask,
            fmt="e5m2", n_kv_heads=KV, mode="rne", kv_mode="stochastic",
            window=0, cap=0.0, impl="kernel", interpret=False, ppb=ppb,
            spb=spb)

    text = _compile_text(
        fused, one_chip, *_page_args(), ((SLOTS, KV, HD), jnp.float32),
        ((SLOTS, KV, HD), jnp.float32), ((SLOTS, 2), jnp.uint32),
        ((SLOTS,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("impl,blocks", [
    ("lns", (128, 128, 128, 16)),
    ("lns_loop", (128, 128, 128)),
    ("fused_dequant", (128, 128, 128)),
])
def test_lns_matmul_compiles(one_chip, impl, blocks):
    def mm(x, w):
        return _lns_matmul(
            x, w, fmt="e4m3", mode="rne", impl=impl,
            blocks=blocks + (16,) * (4 - len(blocks)), interpret=False,
            compute_dtype=jnp.bfloat16, w_fmt="e4m3")

    text = _compile_text(mm, one_chip, ((128, 896), jnp.uint8),
                         ((896, 4864), jnp.uint8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["mul", "rsqrt"])
def test_fp8_elementwise_compiles(one_chip, op):
    def ew(x, y):
        return _fp8_elementwise(op, x, y if op == "mul" else None, fmt="e4m3",
                                mode="rne", block_rows=256, interpret=False)

    text = _compile_text(ew, one_chip, ((8, 4864), jnp.uint8),
                         ((8, 4864), jnp.uint8))
    assert "tpu_custom_call" in text
