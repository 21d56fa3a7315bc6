"""Model operations of the segments completed in the traced window (prompt
prefill, head, decode steps; bench/flops.py) over the window times the
chip's bf16 peak."""
import flops


def read(rec):
    if rec.trace is None:
        return None
    peak = flops.peak(rec.device_kind)
    lo, hi = rec.extra["t0"], rec.extra["t0"] + rec.window_s
    cfgs = rec.extra["configs"]
    ops = sum(flops.request_flops(cfgs[s["tier"]], s["prompt"], s["decoded"])
              for s in rec.segments if lo <= s["finish"] <= hi)
    return 100.0 * ops / (rec.window_s * peak)
