"""95th percentile, over every round of the window, of the host-clock time
from handing a round's arrays to the session to its decisions on the host."""
from record import quantile


def read(rec):
    q = quantile(rec.round_s, 0.95)
    return None if q is None else q * 1e3
