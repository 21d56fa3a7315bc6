"""Prompt plus decoded tokens of every segment completed in the window,
over the window."""


def read(rec):
    return rec.tokens_in_window / rec.window_s if rec.window_s > 0 else None
