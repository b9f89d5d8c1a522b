"""The reader of ``engine.search_steps.sweep``: the mean halvings of the
sweep's two binary searches per ``engine.window`` span, on staged span
records, on records without the counts (as a program that does not
count them writes) and in a tiny traced run of the harness."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import Cell, read_metric, run_cell  # noqa: E402

NAME = "engine.search_steps.sweep"
RUN = NS(window=(0.0, 1.0))


def rec(name, id, start, end, **attrs):
    return NS(name=name, id=id, parent=0, start=start, end=end, attrs=attrs)


def staged(counted: bool = True) -> list:
    """Three sweep windows of 7 + 8, 9 + 9 and 2 + 1 halvings, among spans
    of other names."""
    steps = [(7, 8), (9, 9), (2, 1)] if counted else [None] * 3
    out = [rec("session.execute", 1, 0.0, 0.9)]
    for i, s in enumerate(steps):
        attrs = dict(program="serve_and", window=i, lanes=1000, live_lanes=10)
        if s is not None:
            attrs.update(anchor_steps=s[0], pool_steps=s[1])
        out.append(rec("engine.window", 2 + 2 * i, 0.1 * i, 0.1 * i + 0.05,
                       **attrs))
        out.append(rec("engine.device_wait", 3 + 2 * i, 0.1 * i,
                       0.1 * i + 0.04))
    return out


@pytest.fixture
def ring(monkeypatch):
    from repro.serving import tracing

    state = NS(records=staged(), dropped=False)
    monkeypatch.setattr(tracing, "spans", lambda since, until: tracing.Spans(
        state.records, state.dropped))
    return state


def test_the_mean_over_the_windows(ring):
    assert read_metric(NAME, RUN) == pytest.approx((15 + 18 + 3) / 3)


def test_nothing_where_the_spans_carry_no_counts(ring):
    ring.records = staged(counted=False)
    assert read_metric(NAME, RUN) is None
    ring.records = staged()[:3] + staged(counted=False)[3:]  # some lack them
    assert read_metric(NAME, RUN) is None


def test_nothing_from_an_empty_or_dropping_ring(ring):
    ring.records = [r for r in staged() if r.name != "engine.window"]
    assert read_metric(NAME, RUN) is None
    ring.records = []
    assert read_metric(NAME, RUN) is None
    ring.records, ring.dropped = staged(), True
    assert read_metric(NAME, RUN) is None


def test_a_tiny_traced_run_reports_it(tmp_path, monkeypatch):
    """The served path's own spans: a tiny AND cell reads a positive count,
    at most two capped searches' worth."""
    from bench import harness
    from bench.trace import TraceSummary

    # the CPU's trace holds no device plane and the CPU has no peaks
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
                [{"name": NAME, "unit": "steps"}])
    result = run_cell(cell, seed=2**31 + 11, seconds=1.0, trace=True,
                      cache=tmp_path / "cache", compile_cache=False)
    assert result["correct"] is True, result["checks"]
    assert 0 < result["metrics"][NAME]["value"] <= 64
