"""Device time of the held experts in the cloud pool's decode programs
(operations under the program's ``moe.experts`` scope, read by
``routed_moe_pools.expert_device_s``), per Moonlight decode step, in the
traced window."""
from systems import routed_moe_pools as moe


def read(rec):
    s = moe.expert_device_s(rec)
    n = rec.trace.span_count(moe.DECODE_SPAN) if s is not None else 0
    return s * 1e6 / n if n else None
