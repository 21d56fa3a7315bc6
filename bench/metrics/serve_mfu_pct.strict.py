"""Model operations of the segments completed in the traced window over
the window times the chip's bf16 peak: the edge pool's counted by
``flops.py``, the cloud pool's (Moonlight at this chip's held share of the
experts) by ``flops_moe.py``."""
import flops
import flops_moe

CLOUD = 1


def read(rec):
    if rec.trace is None:
        return None
    peak = flops.peak(rec.device_kind)
    lo, hi = rec.extra["t0"], rec.extra["t0"] + rec.window_s
    cfgs = rec.extra["configs"]
    ops = sum((flops_moe if s["tier"] == CLOUD else flops).request_flops(
        cfgs[s["tier"]], s["prompt"], s["decoded"])
        for s in rec.segments if lo <= s["finish"] <= hi)
    return 100.0 * ops / (rec.window_s * peak)
