"""95th percentile of a segment's wait in its pool's queue (the executor's
admit time less its enqueue time)."""
from record import quantile


def read(rec):
    q = quantile([s["admit"] - s["enqueue"] for s in rec.segments], 0.95)
    return None if q is None else q * 1e3
