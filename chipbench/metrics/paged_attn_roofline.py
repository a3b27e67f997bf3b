"""The paged write+attend kernel's share of its roofline in the traced
slice: the least time its calls could take (bytes they need at the HBM
peak, or ops at the int8 peak if larger) over its device time (%)."""
from chipbench import steps


def read(run):
    t = steps.kernel_seconds(run)
    if not t:
        return None
    need = steps.traced_cost(run)
    least = max(need["kernel_bytes"] / run.peaks.hbm_bytes,
                need["kernel_ops"] / run.peaks.int8_ops)
    return 100.0 * least / t
