"""Device time of the c6_tail Pallas kernel per round traced (it runs only
in rounds whose draw exceeds the uplink budget)."""

PATTERN = r"^%c6_tail\b"


def read(rec):
    t = rec.trace
    if t is None:
        return None
    s = t.time_s(PATTERN)
    n = t.span_count("bench.round")
    return None if s is None or not n else s * 1e6 / n
