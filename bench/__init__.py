"""The benchmark: cells, traffic, reference, and the reduction of runs
and traces to metrics.  ``python3 bench/run.py --help``."""
