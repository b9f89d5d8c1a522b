"""Process start to the opening of the window (s): generating the
documents, building, persisting and opening the index, warm-up and any
compilation."""


def read(run):
    return run.setup_s
