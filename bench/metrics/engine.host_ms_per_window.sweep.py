"""Host time per sweep window (ms): each ``engine.window`` span less its
``engine.device_wait``, that is dispatch, the copy of the outputs to the
host and the per-query masking, plus each batch's ``engine.merge``
shared over its windows."""

from bench.spans import host_ms_per_window, window_spans


def read(run):
    records = window_spans(run)
    return None if records is None else host_ms_per_window(records)
