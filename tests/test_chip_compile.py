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
from repro.kernels.autotune import PAGED_BLOCKS
from repro.kernels.fp8_elementwise import _fp8_elementwise
from repro.kernels.lns_matmul import _lns_matmul

KV, G, HD, PAGE = 2, 7, 64, 16  # qwen2-0.5b attention geometry, 16-token pages
SLOTS, MAXP = 8, 32             # 8 slots of up to 512 tokens
POOL = 65536                    # pages: a deployment-sized pool
# the decode-long cell: 64 slots of up to 1536 tokens, a pool sized to them
LONG_SLOTS, LONG_MAXP = 64, 96
LONG_POOL = LONG_SLOTS * LONG_MAXP + 1


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


def _page_args(slots=SLOTS, maxp=MAXP, pool=POOL, kv=KV, g=G, hd=HD):
    return [((slots, 1, kv * g, hd), jnp.bfloat16),
            ((pool, PAGE, kv, hd), jnp.uint8), ((pool, PAGE, kv, hd), jnp.uint8),
            ((pool,), jnp.float32), ((pool,), jnp.float32),
            ((slots, maxp), jnp.int32), ((slots,), jnp.int32)]


def _compile_unfused(one_chip, ppb, spb, kv=KV, **geom):
    """(pages per walked block, slots per program) of the live-page
    walk."""
    def unfused(q, kp, vp, ks, vs, bt, ln):
        return pa._paged_decode_attention(
            q, kp, vp, ks, vs, bt, ln, fmt="e5m2", n_kv_heads=kv, mode="rne",
            window=0, cap=0.0, impl="kernel", interpret=False, ppb=ppb,
            spb=spb)

    return _compile_text(unfused, one_chip, *_page_args(kv=kv, **geom))


def _compile_fused(one_chip, ppb, spb, slots=SLOTS, kv=KV, hd=HD,
                   **geom):
    def fused(q, kp, vp, ks, vs, bt, ln, k_new, v_new, keys, mask):
        return pa._fused_decode_write_attend(
            q, k_new, v_new, kp, vp, ks, vs, bt, ln, keys, keys, mask,
            fmt="e5m2", n_kv_heads=kv, mode="rne", kv_mode="stochastic",
            window=0, cap=0.0, impl="kernel", interpret=False, ppb=ppb,
            spb=spb)

    return _compile_text(
        fused, one_chip, *_page_args(slots=slots, kv=kv, hd=hd, **geom),
        ((slots, kv, hd), jnp.float32), ((slots, kv, hd), jnp.float32),
        ((slots, 2), jnp.uint32), ((slots,), jnp.bool_))


@pytest.mark.parametrize("ppb,spb", [(1, 1), (2, 1), (4, 2), (1, 4)])
def test_paged_kernel_compiles(one_chip, ppb, spb):
    text = _compile_unfused(one_chip, ppb, spb)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ppb,spb", [(1, 1), (2, 2)])
def test_fused_write_attend_kernel_compiles(one_chip, ppb, spb):
    text = _compile_fused(one_chip, ppb, spb)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("ppb,spb", [(1, 1), (8, 4)])
def test_paged_kernel_compiles_at_decode_long(one_chip, ppb, spb, fused):
    """The decode-long cell's geometry (64 slots x 96 pages), at the
    served block shape's neighbours, each slot's 96 pages folded into one
    partial."""
    compile_ = _compile_fused if fused else _compile_unfused
    text = compile_(one_chip, ppb, spb, slots=LONG_SLOTS, maxp=LONG_MAXP,
                    pool=LONG_POOL)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("geom", [
    dict(kv=1, g=7, hd=64, slots=64, maxp=96),
    dict(kv=8, g=5, hd=128, slots=16, maxp=32),
    dict(kv=8, g=5, hd=128, slots=8, maxp=2048),
], ids=["qwen2-tp2-shard", "qwen3-14b", "qwen3-14b-32k"])
def test_paged_kernel_compiles_at_other_geometries(one_chip, geom, fused):
    """The served block shape at the other geometries the kernel runs: a
    qwen2 shard of a 2-way tensor-parallel mesh (1 KV head, KV*hd = 64:
    pages gathered as [8, 128] rows), qwen3-14b's 8 KV heads of 128, and
    qwen3-14b at 32k-token contexts, whose 2048 pages a slot fold into
    the same one-partial output block as 32 pages."""
    compile_ = _compile_fused if fused else _compile_unfused
    ppb, spb = PAGED_BLOCKS
    geom = dict(geom, pool=geom["slots"] * geom["maxp"] + 1)
    text = compile_(one_chip, ppb, spb, **geom)
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


# DeepSeek-V2-Lite's latent attention: one KV head, 16 query heads, rope
# keys of 64 and latents of 512 lanes; the decode-long cell's 64 x 96 pages
MLA_H, MLA_DR, MLA_L = 16, 64, 512


def _mla_args(slots=LONG_SLOTS, maxp=LONG_MAXP):
    pool = slots * maxp + 1
    return [((slots, 1, MLA_H, MLA_DR + MLA_L), jnp.float32),
            ((pool, PAGE, MLA_DR), jnp.uint8), ((pool, PAGE, MLA_L), jnp.uint8),
            ((pool,), jnp.float32), ((pool,), jnp.float32),
            ((slots, maxp), jnp.int32), ((slots,), jnp.int32)]


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_paged_kernel_compiles_in_mla_mode(one_chip, fused):
    """The kernel's MLA mode at the served block shape: the 64-lane rope
    key pages gathered as [8, 128] rows, the latent pages as [16, 512],
    the query [q_pe ‖ q_eff] of 576 lanes."""
    ppb, spb = PAGED_BLOCKS
    kw = dict(fmt="e5m2", n_kv_heads=1, mode="rne", window=0, cap=0.0,
              impl="kernel", interpret=False, ppb=ppb, spb=spb,
              scale=0.1)
    if fused:
        def fn(q, kp, vp, ks, vs, bt, ln, k_new, v_new, keys, mask):
            return pa._fused_decode_write_attend(
                q, k_new, v_new, kp, vp, ks, vs, bt, ln, keys, keys, mask,
                kv_mode="stochastic", **kw)

        extra = [((LONG_SLOTS, 1, MLA_DR), jnp.float32),
                 ((LONG_SLOTS, 1, MLA_L), jnp.float32),
                 ((LONG_SLOTS, 2), jnp.uint32), ((LONG_SLOTS,), jnp.bool_)]
    else:
        def fn(q, kp, vp, ks, vs, bt, ln):
            return pa._paged_decode_attention(q, kp, vp, ks, vs, bt, ln, **kw)

        extra = []
    text = _compile_text(fn, one_chip, *_mla_args(), *extra)
    assert "tpu_custom_call" in text
