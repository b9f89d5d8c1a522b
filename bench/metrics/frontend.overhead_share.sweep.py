"""Share of the window in which no Session.execute runs (%): the host is
in the frontend, the event loop or the clients."""

from bench.stats import covered_s


def read(run):
    lo, hi = run.window
    if hi <= lo:
        return None
    return 100.0 * (1.0 - covered_s(run.exec_spans, lo, hi) / (hi - lo))
