"""Halvings the sweep's two binary searches run per device step: the mean
of ``anchor_steps + pool_steps`` over the window's ``engine.window``
spans.  None where the spans carry no such counts."""

from bench.spans import window_spans


def read(run):
    records = window_spans(run)
    if records is None:
        return None
    windows = [r for r in records if r.name == "engine.window"]
    if not windows or any("anchor_steps" not in w.attrs for w in windows):
        return None
    return sum(w.attrs["anchor_steps"] + w.attrs["pool_steps"]
               for w in windows) / len(windows)
