"""The paged write+attend kernel's share of its roofline in its MLA mode,
in the traced slice: the least time its calls could take (the latent and
rope-key pages, scales, query, new row and output they need at the HBM
peak, or their ops at the int8 peak if larger; ``mla_costs.py``) over
its device time (%).  Nothing to read for a model without latent
attention."""
from chipbench import mla_costs, steps


def read(run):
    if "kv_lora_rank" not in run.cell.model:
        return None
    t = steps.kernel_seconds(run)
    if not t:
        return None
    kb, ko = mla_costs.traced_cost(run)
    least = max(kb / run.peaks.hbm_bytes, ko / run.peaks.int8_ops)
    return 100.0 * least / t
