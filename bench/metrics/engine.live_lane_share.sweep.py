"""Share of the sweep windows' padded lanes that hold a posting of a
query's driving list (%): the sum of ``live_lanes`` over the sum of
``lanes`` of the window's ``engine.window`` spans."""

from bench.spans import live_lane_share, window_spans


def read(run):
    records = window_spans(run)
    return None if records is None else live_lane_share(records)
