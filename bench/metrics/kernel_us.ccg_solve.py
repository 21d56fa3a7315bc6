"""Device time of the ccg_solve Pallas kernel per round traced."""

PATTERN = r"^%ccg_solve\b"


def read(rec):
    t = rec.trace
    if t is None:
        return None
    s = t.time_s(PATTERN)
    n = t.span_count("bench.round")
    return None if s is None or not n else s * 1e6 / n
