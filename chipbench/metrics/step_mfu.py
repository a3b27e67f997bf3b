"""Model FLOP utilization of the whole serving step in the traced slice:
useful FLOPs of the valid tokens processed (2 per matmul parameter,
attention over each token's context, the LM head per sampled token) over
the slice's length and the chip's bf16 peak (%)."""
from chipbench import steps


def read(run):
    tr = run.trace
    if (tr is None or tr.devices == 0 or tr.window_s <= 0
            or not run.traced_steps()):
        return None
    return 100.0 * steps.traced_cost(run)["flops"] / tr.window_s \
        / run.peaks.bf16_flops
