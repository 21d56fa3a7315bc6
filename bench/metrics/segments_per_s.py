"""Cameras x rounds completed in the window, over the window."""


def read(rec):
    if rec.window_s <= 0:
        return None
    return rec.rounds * rec.extra["cameras"] / rec.window_s
