"""Share of the roofline in the pools' decode steps: the least time the
chip needs for the window's decode steps, over the device time of every
``_decode_slab_impl`` execution in the traced window.

Per pool, the least time is the bytes its steps must read
(``flops.decode_bytes``: the weights at bfloat16 once a step, the keys and
values each served segment attends to) over HBM bandwidth, or the
segments' decode operations over the bf16 peak where that is larger; the
steps per pool are the cell's count (``Record.extra["decode_steps"]``).
"""
import flops

PATTERN = r"_decode_slab_impl"


def read(rec):
    t = rec.trace
    steps = rec.extra.get("decode_steps")
    if t is None or not steps or not any(steps.values()):
        return None
    s = t.time_s(PATTERN, modules=True)
    if not s:
        return None
    bw = flops.peak(rec.device_kind, "hbm_bytes_per_s")
    peak = flops.peak(rec.device_kind)
    least = 0.0
    for tier, n in steps.items():
        c = rec.extra["configs"][tier]
        reqs = [(x["prompt"], x["decoded"]) for x in rec.segments
                if x["tier"] == tier]
        ops = sum(flops.decode_flops(c, p, d) for p, d in reqs)
        least += max(flops.decode_bytes(c, n, reqs) / bw, ops / peak)
    return 100.0 * least / s
