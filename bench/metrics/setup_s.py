"""Set-up: process start to the window's start -- imports, compile cache,
weights and traffic from the seed, warm-up (and compiles, where the cache
is cold)."""


def read(rec):
    return rec.setup_s
