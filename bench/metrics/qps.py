"""Answered queries over the whole window, from its opening to the
answer of the last query sent inside it (q/s)."""


def read(run):
    return run.answered / run.window_s if run.window_s > 0 and run.answered else None
