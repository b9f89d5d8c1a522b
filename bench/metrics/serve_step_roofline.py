"""Least time of the AND step over the device time of the window's
programs (%): the least time moves each query term's compressed list once
and writes each answer once at the HBM peak (bench/roofline.py).  Every
device program of the window counts in the device time, so a helper
program the session launches beside the sweep lowers the share; it never
raises it."""

from bench.stats import roofline_pct


def read(run):
    return roofline_pct(run)
