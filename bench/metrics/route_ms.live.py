"""Host time of routing a round (route_many and its decisions to the host),
mean over the rounds of the traced window."""


def read(rec):
    if not rec.route_s:
        return None
    return sum(rec.route_s) / len(rec.route_s) * 1e3
