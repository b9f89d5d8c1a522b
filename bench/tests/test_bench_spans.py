"""The readers of the program's spans: on hand-made span lists whose
answers are counted by hand, on an empty or a dropping ring and on a
program without the recorder, and in a tiny traced run of the harness."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import Cell, read_metric, run_cell  # noqa: E402

FOUR = {"engine.live_lane_share.sweep": "%",
        "engine.host_ms_per_window.sweep": "ms",
        "session.plan_ms.sweep": "ms", "frontend.queue_ms.sweep": "ms"}


def rec(name, id, parent, start, end, **attrs):
    return NS(name=name, id=id, parent=parent, start=start, end=end,
              attrs=attrs)


def hand_made():
    """One batch of two sweep windows (400 ms each, 370 ms of it waiting
    for the device), a 40 ms merge, 3 ms of planning and 1 ms of encoding
    in the call and 2 ms of planning in a nested segment call; three
    requests queued 5, 1 and 3 ms."""
    return [
        rec("frontend.queue", 20, 0, 0.000, 0.005, batch=1),
        rec("frontend.queue", 21, 0, 0.004, 0.005, batch=1),
        rec("frontend.queue", 22, 0, 0.002, 0.005, batch=1),
        rec("frontend.batch", 1, 0, 0.005, 1.0, batch=1, queries=3),
        rec("session.execute", 2, 1, 0.006, 0.99, queries=3),
        rec("session.plan", 3, 2, 0.006, 0.009),
        rec("engine.encode", 4, 2, 0.009, 0.010),
        rec("engine.window", 5, 2, 0.02, 0.42, lanes=1000, live_lanes=10),
        rec("engine.device_wait", 6, 5, 0.03, 0.40),
        rec("engine.window", 7, 2, 0.45, 0.85, lanes=1000, live_lanes=30),
        rec("engine.device_wait", 8, 7, 0.46, 0.83),
        rec("engine.merge", 9, 2, 0.86, 0.90),
        rec("session.execute", 10, 2, 0.91, 0.95, queries=3),
        rec("session.plan", 11, 10, 0.91, 0.912),
    ]


@pytest.fixture
def ring(monkeypatch):
    """Hands the readers ``ring.records`` (and ``ring.dropped``) in place
    of the program's ring, and records the interval they asked for."""
    from repro.serving import tracing

    state = NS(records=hand_made(), dropped=False, asked=None)

    def spans(since, until):
        state.asked = (since, until)
        return tracing.Spans(state.records, state.dropped)

    monkeypatch.setattr(tracing, "spans", spans)
    return state


RUN = NS(window=(0.0, 1.0))


@pytest.mark.parametrize("name,want", [
    ("engine.live_lane_share.sweep", 2.0),  # 40 of 2,000 lanes
    ("engine.host_ms_per_window.sweep", 50.0),  # (800 - 740 + 40) ms / 2
    ("session.plan_ms.sweep", 6.0),  # (3 + 1 + 2) ms over one outer call
    ("frontend.queue_ms.sweep", 3.0),  # median of 5, 1, 3 ms
])
def test_reader_on_a_hand_made_span_list(ring, name, want):
    assert read_metric(name, RUN) == pytest.approx(want)
    assert ring.asked == RUN.window


@pytest.mark.parametrize("name", FOUR)
def test_reader_reads_nothing_from_an_empty_or_dropping_ring(ring, name):
    ring.records = []
    assert read_metric(name, RUN) is None
    ring.records, ring.dropped = hand_made(), True
    assert read_metric(name, RUN) is None


@pytest.mark.parametrize("name", FOUR)
def test_reader_reads_nothing_from_a_program_without_the_recorder(
        monkeypatch, tmp_path, name):
    """As over an older checkout: ``repro.serving`` holds no ``tracing``."""
    import repro.serving

    monkeypatch.delitem(sys.modules, "repro.serving.tracing", raising=False)
    monkeypatch.delattr(repro.serving, "tracing", raising=False)
    monkeypatch.setattr(repro.serving, "__path__", [str(tmp_path)])
    with pytest.raises(ImportError):
        from repro.serving import tracing  # noqa: F401
    assert read_metric(name, RUN) is None


def test_a_tiny_traced_run_reports_the_four_metrics(tmp_path, monkeypatch):
    from bench import harness
    from bench.trace import TraceSummary

    # The CPU's trace holds no device plane and the CPU has no peaks: both
    # stand in here, for the readers of the trace.  The four metrics read
    # the program's ring.
    monkeypatch.setattr(harness, "reduce_file", lambda path: TraceSummary(
        window_s=1.0, busy_s=1.0, devices=1, executions=2, execution_s=0.5,
        execute_spans=1))
    monkeypatch.setattr(harness.roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    config = {"name": "tiny-archive",
              "collection": {"articles": 3, "versions_per_article": 10,
                             "words_per_doc": 30, "vocab_size": 60,
                             "zipf": 1.1, "edit_rate": 0.05,
                             "structure": "linear", "vocab_seed": 7},
              "index": {"kind": "nonpositional", "store": "repair_skip"},
              "frontend": {"max_batch": 4}}
    traffic = {"kind": "and", "terms": 2, "words": "low", "loop": "closed",
               "clients": 4, "rounds": 3, "warmup": [4]}
    cell = Cell("tiny.and-low", json.loads(json.dumps(config)), traffic, [],
                [{"name": n, "unit": u} for n, u in FOUR.items()])
    result = run_cell(cell, seed=2**31 + 9, seconds=1.0, trace=True,
                      cache=tmp_path / "cache", compile_cache=False)
    assert result["correct"] is True, result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == set(FOUR)
    assert 0 < metrics["engine.live_lane_share.sweep"] <= 100
    assert metrics["engine.host_ms_per_window.sweep"] > 0
    assert metrics["session.plan_ms.sweep"] > 0
    assert metrics["frontend.queue_ms.sweep"] >= 0
