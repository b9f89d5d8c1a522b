"""The trace reduction, on a hand-made trace whose answers are counted by
hand.  ``bench/tests/data/record_tiny_and.py`` records a small trace of a
tiny AND cell on a TPU for a test beside this one."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.trace import breakdown, reduce_planes  # noqa: E402


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def hand_made():
    """A 100 ns window: two execute spans (10-40, 60-90) and requests
    outstanding at 5-45 and 55-95; programs at 20-30 and 65-85 with ops
    inside; one program before the window."""
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[ev("bench.window", 0, 100),
                                ev("bench.pending", 5, 40),
                                ev("bench.pending", 55, 40)]),
        NS(name="exec", events=[ev("bench.execute", 10, 30),
                                ev("bench.execute", 60, 30)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_counted", -50, 10),
                                       ev("jit_counted", 20, 10),
                                       ev("jit_counted", 65, 20)]),
        NS(name="XLA Ops", events=[ev("fusion.1", -50, 10), ev("fusion.1", 20, 6),
                                   ev("gather.2", 24, 6), ev("gather.2", 65, 20)]),
    ])
    return [host, dev]


def test_hand_made_trace():
    s = reduce_planes(hand_made())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)  # 20-30 and 65-85
    assert s.devices == 1
    assert s.executions == 2 and s.execution_s == pytest.approx(30e-9)
    assert s.execute_spans == 2
    assert s.modules == {"jit_counted": 2}
    assert s.op_s == pytest.approx({"gather.2": 26e-9, "fusion.1": 6e-9})
    # idle: 0-20 (mid 10 is the start of an execute: counted as execute),
    # 30-65 (mid 47.5: nothing outstanding, the load generator's),
    # 85-100 (mid 92.5: outstanding, not executing: the frontend's)
    assert s.idle_gaps == [("load generator", pytest.approx(35e-9)),
                           ("execute", pytest.approx(20e-9)),
                           ("frontend", pytest.approx(15e-9))]
    b = breakdown(s)
    assert b["device_ops"][0][0] == "gather.2" and len(b["idle_gaps"]) == 3


def test_no_window_span_is_an_error():
    planes = hand_made()
    planes[0].lines[0].events = []
    with pytest.raises(ValueError, match="bench.window"):
        reduce_planes(planes)
