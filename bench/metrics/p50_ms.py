"""Median latency of all answered requests, each from when it was due (ms)."""

from bench.stats import percentile


def read(run):
    p = percentile(run.latencies_s, 50)
    return None if p is None else 1e3 * p
