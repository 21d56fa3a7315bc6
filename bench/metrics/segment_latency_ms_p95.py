"""95th percentile, over every segment due in the window, of the time from
its round's due time to its completion; a segment never served counts as a
miss (infinitely late), so more than 5% failed reads None."""
import math

from record import quantile


def read(rec):
    lat = [s["finish"] - s["due"] for s in rec.segments]
    lat += [math.inf] * rec.failed
    q = quantile(lat, 0.95)
    return None if q is None or math.isinf(q) else q * 1e3
