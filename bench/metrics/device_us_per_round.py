"""Device busy time (union of operations) per round traced: the compiled
decide program's device time, transfers included."""


def read(rec):
    t = rec.trace
    if t is None or t.busy_s is None:
        return None
    n = t.span_count("bench.round")
    return t.busy_s * 1e6 / n if n else None
