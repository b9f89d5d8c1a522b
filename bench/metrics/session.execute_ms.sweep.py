"""Mean duration of one Session.execute call in the window (ms)."""

from bench.stats import mean_ms


def read(run):
    return mean_ms(run.exec_spans)
