"""Device time of one slab-decode program (both pools' executions
together), per execution, in the traced window."""

PATTERN = r"_decode_slab_impl"


def read(rec):
    t = rec.trace
    if t is None:
        return None
    s = t.time_s(PATTERN, modules=True)
    n = t.count(PATTERN, modules=True)
    return None if s is None or not n else s * 1e6 / n
