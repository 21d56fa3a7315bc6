"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    t = rec.trace
    idle = None if t is None else t.idle_share
    return None if idle is None else 100.0 * idle
