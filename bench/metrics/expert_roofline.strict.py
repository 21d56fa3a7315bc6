"""Share of the roofline in the held experts of the cloud pool's decode
steps: the least time the chip needs for them over their device time
(``routed_moe_pools.expert_device_s``) in the traced window.

The least time is the larger of the bytes at bfloat16 of the experts that
got any row (one read per expert, layer and step) over HBM bandwidth, and
the operations of the rows they computed over the bf16 peak
(``flops_moe.expert_bytes``, ``expert_flops``), from the cloud slab's
counters over the window's decode steps (``Record.extra``)."""
import flops
import flops_moe
from systems import routed_moe_pools as moe


def read(rec):
    counts = rec.extra.get("expert_counters")
    s = moe.expert_device_s(rec)
    if not counts or s is None:
        return None
    c = rec.extra["configs"][moe.CLOUD]
    least = max(flops_moe.expert_bytes(c, counts["experts_hit"])
                / flops.peak(rec.device_kind, "hbm_bytes_per_s"),
                flops_moe.expert_flops(c, counts["expert_rows"])
                / flops.peak(rec.device_kind))
    return 100.0 * least / s
