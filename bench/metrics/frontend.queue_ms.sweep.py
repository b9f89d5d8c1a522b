"""Median time a request waits in the frontend, from submit to its batch
starting on the executor thread (ms): the ``frontend.queue`` spans."""

from bench.spans import queue_ms, window_spans


def read(run):
    records = window_spans(run)
    return None if records is None else queue_ms(records)
