"""The paged kernel's and the step's op/byte counts against hand counts."""
import numpy as np
import pytest

from chipbench import costs

QWEN2 = costs.Dims(layers=24, d_model=896, heads=14, kv_heads=2,
                   head_dim=64, d_ff=4864, vocab=151936)


@pytest.mark.parametrize("ctx,pages", [(0, 1), (15, 1), (16, 2), (850, 54)])
def test_paged_kernel_bytes_and_ops_by_hand(ctx, pages):
    b, o = costs.paged_kernel_cost(np.array([ctx]), QWEN2, 16)
    row = 2 * 64  # KV heads x head_dim, one byte per code
    kv = 2 * pages * (16 * row + 4)
    assert b == kv + 14 * 64 + 2 * row + 14 * 64 * 4
    assert o == 4 * 14 * 64 * (ctx + 1)


def test_paged_kernel_sums_lanes():
    one = [costs.paged_kernel_cost(np.array([c]), QWEN2, 16)
           for c in (3, 100, 700)]
    both = costs.paged_kernel_cost(np.array([3, 100, 700]), QWEN2, 16)
    assert both == (sum(x[0] for x in one), sum(x[1] for x in one))


def test_step_cost_decode_by_hand():
    lengths = np.array([10, 0, 40])
    n_new = np.array([1, 0, 1])
    c = costs.step_cost(lengths, n_new, 2, QWEN2, 16)
    b, o = costs.paged_kernel_cost(np.array([10, 40]), QWEN2, 16)
    assert c["kernel_bytes"] == 24 * b and c["kernel_ops"] == 24 * o
    assert c["tokens"] == 2
    per_layer = (896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864)
    assert QWEN2.layer_matmul_params() == per_layer
    attn = 4 * 14 * 64 * (11 + 41) * 24
    assert c["flops"] == 2 * per_layer * 24 * 2 + attn + 2 * 896 * 151936 * 2


def test_step_cost_prefill_chunk_counts_each_sub_step():
    c = costs.step_cost(np.array([5, 7]), np.array([3, 1]), 1, QWEN2, 16)
    assert c["tokens"] == 4  # lane 0 feeds 3 tokens, lane 1 one
    ctx = np.array([5, 7, 6, 7])  # sub-step 0: both lanes; 1, 2: lane 0
    b, o = costs.paged_kernel_cost(ctx, QWEN2, 16)
    assert c["kernel_ops"] == 24 * o
