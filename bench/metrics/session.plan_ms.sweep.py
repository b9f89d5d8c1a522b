"""Parse, plan and encode time per Session.execute call (ms): the
``session.plan`` and ``engine.encode`` spans over the number of
``session.execute`` spans in the window."""

from bench.spans import plan_ms, window_spans


def read(run):
    records = window_spans(run)
    return None if records is None else plan_ms(records)
