"""Device bytes the session's servers hold after set-up, over the
collection's text bytes, in percent (the paper's space measure, on the
device)."""


def read(run):
    return 100.0 * run.device_bytes / run.text_bytes if run.device_bytes else None
