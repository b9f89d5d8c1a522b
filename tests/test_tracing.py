"""The served path's spans: the tree a micro-batch leaves in the ring, the
lane counts of the sweep window, the ring's bound, the spans on the
profiler's host plane, and the names of the device programs."""

import asyncio
import re
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.serving import tracing
from repro.serving.engine import (
    AND,
    MAX_CAND_ROWS,
    PHRASE,
    RANK,
    BatchedServer,
    window_lanes,
)
from repro.serving.frontend import FrontendConfig, MicroBatchFrontend
from repro.serving.session import Session

from lane_budget import lane_budget_windows


def _docs(n: int = 700):
    """``n`` documents: ``common`` in each, ``often`` and ``some`` in a
    random 60% and 30% (lists of over 64 C entries, so a dense sweep takes
    two windows), ``rare`` in the first 10, and runs the grammar
    compresses."""
    rng = np.random.default_rng(7)
    docs = []
    for d in range(n):
        words = ["common"]
        words += ["often"] if rng.random() < 0.6 else []
        words += ["some"] if rng.random() < 0.3 else []
        words += ["rare"] if d < 10 else []
        docs.append(" ".join(words + ["alpha", "beta", "gamma"][: 1 + d % 3]))
    return docs


@pytest.fixture(scope="module")
def index():
    return NonPositionalIndex.build(_docs(), store="repair_skip")


@pytest.fixture(scope="module")
def session(index):
    return Session.build(index)


QUERIES = ["often common", "some often", "rare alpha", "common beta"]


def _serve(session, queries, max_batch):
    async def go():
        async with MicroBatchFrontend(session, FrontendConfig(
                max_batch=max_batch, max_delay=5.0)) as fe:
            return fe, await asyncio.gather(*(fe.submit(q) for q in queries))

    return asyncio.run(go())


def test_a_micro_batch_leaves_one_span_tree(session):
    t0 = time.perf_counter()
    fe, answers = _serve(session, QUERIES, max_batch=len(QUERIES))
    got = tracing.spans(t0, time.perf_counter())
    assert not got.dropped
    recs = got.records
    by_id = {r.id: r for r in recs}
    named = lambda n: [r for r in recs if r.name == n]  # noqa: E731

    batch, = named("frontend.batch")
    bid = batch.attrs["batch"]
    assert batch.attrs["queries"] == len(QUERIES)
    execute, = named("session.execute")
    assert execute.parent == batch.id
    plan, = named("session.plan")
    assert plan.parent == execute.id
    windows = named("engine.window")
    assert windows and all(w.parent == execute.id for w in windows)
    for w in windows:
        waits = [r for r in named("engine.device_wait") if r.parent == w.id]
        fetches = [r for r in named("engine.fetch") if r.parent == w.id]
        assert len(waits) == 1 and len(fetches) == 1
        assert w.start <= waits[0].start <= waits[0].end <= fetches[0].start
        assert fetches[0].end <= w.end
        assert w.attrs["program"] == "serve_and" and w.attrs["batch"] == bid
    assert sorted(w.attrs["window"] for w in windows) == list(range(len(windows)))
    merge, = named("engine.merge")
    assert merge.parent == execute.id and merge.start >= windows[-1].end
    encode, = named("engine.encode")
    assert encode.parent == execute.id

    # every queued request names the batch that took it, and its request
    requests = {r.attrs["request"]: r for r in named("frontend.request")}
    queues = named("frontend.queue")
    assert len(queues) == len(QUERIES) == len(requests)
    for q in queues:
        assert q.attrs["batch"] == bid
        req = by_id[q.parent]
        assert req.name == "frontend.request"
        assert req.attrs == {"request": q.attrs["request"], "batch": bid}
        assert req.start == q.start and q.end <= batch.start <= req.end

    # one window span per window the sweep needed: the fused layout's lane
    # budget, max_phrase rounded up, holds each of these lists whole
    server = session.server
    assert server.layout == "fused"
    qt, ql, ok = server.encode([q.split() for q in QUERIES], sort_by_length=True)
    assert len(windows) == server._n_windows(qt, ql, ok) == 1

    # the frontend's latency samples are the request spans
    lat = fe.metrics()["latency"]
    assert lat["count"] == len(QUERIES)
    durations = sorted(r.seconds for r in requests.values())
    assert lat["max_ms"] == round(1e3 * durations[-1], 3)
    for q, a in zip(QUERIES, answers):
        assert np.array_equal(a, session.execute(q))


@pytest.fixture(scope="module")
def postings_index():
    """2,200 documents with no grammar: every C entry holds one posting,
    so ``often``'s list outgrows one fused window of 1,024 lanes."""
    return NonPositionalIndex.build(_docs(2200), store="repair_skip",
                                    max_rules=0)


@pytest.mark.parametrize("layout", ["fused", "dense", "fused-postings"])
def test_live_lanes_match_a_brute_count(index, postings_index, layout):
    if layout == "fused-postings":
        index, layout = postings_index, "fused"
    server = BatchedServer.from_index(index, layout=layout, expand_len=8)
    queries = [q.split() for q in QUERIES] + [["nosuchword", "common"]]
    t0 = time.perf_counter()
    server.conjunctive(queries)
    windows = [r for r in tracing.spans(t0, time.perf_counter()).records
               if r.name == "engine.window"]
    qt, ql, ok = server.encode(queries, sort_by_length=True)
    assert not ok[-1]
    assert len(windows) == server._n_windows(qt, ql, ok)
    c_off = np.asarray(server.arrays["c_offsets"])
    row_len = (np.asarray(server.arrays["c_len"]) if layout == "fused"
               else np.asarray(server.arrays["expand_valid"]).sum(axis=1))
    # the rows each window swept, over the active queries' driving lists
    swept = [[] for _ in windows]
    for first in qt[ok, 0]:
        if layout == "fused":
            for w, rows in enumerate(lane_budget_windows(server, first)):
                swept[w] += rows
        else:
            for row in range(c_off[first], c_off[first + 1]):
                swept[(row - c_off[first]) // MAX_CAND_ROWS].append(row)
    for w in windows:
        if layout == "fused":
            assert w.attrs["lanes"] == len(queries) * window_lanes(
                server.max_phrase)
        else:
            assert w.attrs["lanes"] == (len(queries) * MAX_CAND_ROWS
                                        * server.arrays["expand"].shape[-1])
        live = sum(int(row_len[row]) for row in swept[w.attrs["window"]])
        assert w.attrs["live_lanes"] == live
    # over all windows, every posting of each active query's rarest list
    rarest = [min(len(index.query_word(t)) for t in q)
              for q, o in zip(queries, ok) if o]
    assert sum(w.attrs["live_lanes"] for w in windows) == sum(rarest)
    if index is postings_index:
        assert len(windows) == 2


def test_the_ring_is_bounded_and_says_when_it_dropped(monkeypatch):
    ring = tracing.Recorder(capacity=8)
    monkeypatch.setattr(tracing, "_recorder", ring)
    ends = []
    with tracing.span("unit", i=-1):
        pass
    assert not tracing.spans().dropped  # nothing dropped yet, at any since
    ring = tracing.Recorder(capacity=8)
    monkeypatch.setattr(tracing, "_recorder", ring)
    for i in range(20):
        with tracing.span("unit", i=i):
            pass
        ends.append(time.perf_counter())
    everything = tracing.spans()
    assert [r.attrs["i"] for r in everything.records] == list(range(12, 20))
    assert everything.dropped
    # spans 0-11 fell out: an interval that starts after the 12th ended
    # lost nothing, one that starts before it may have
    assert not tracing.spans(ends[11]).dropped
    assert tracing.spans(ends[10]).dropped
    assert len(tracing.spans(ends[15]).records) == 4


def test_spans_reach_the_profilers_host_plane(session, tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation

    session.execute(QUERIES)  # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.execute"):
            session.execute(QUERIES)
    finally:
        jax.profiler.stop_trace()
    xplane, = Path(tmp_path).rglob("*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(ev)
    outer, = events["bench.execute"]
    lo, hi = outer.start_ns, outer.start_ns + outer.duration_ns
    for name in ("session.execute", "session.plan", "engine.encode",
                 "engine.window", "engine.device_wait", "engine.fetch",
                 "engine.merge"):
        assert events.get(name), f"{name} is not on the host plane"
        for ev in events[name]:
            assert lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi
    stats = dict(events["engine.window"][0].stats)
    assert stats["program"] == "serve_and" and stats["traced"] == 0
    assert {"lanes", "live_lanes", "window"} <= set(stats)


@pytest.fixture(scope="module")
def servers(index):
    """Servers of their own, so that every step below traces anew."""
    return {AND: BatchedServer.from_index(index),
            PHRASE: BatchedServer.from_index(
                PositionalIndex.build(_docs(), store="repair_skip"))}


@pytest.mark.parametrize("kind,topk,doclist,name", [
    (AND, 0, False, "serve_and"), (AND, 0, True, "serve_and_docs"),
    (AND, 10, False, "serve_and_top"), (RANK, 10, False, "rank_bm25"),
    (PHRASE, 0, False, "serve_phrase"), (PHRASE, 0, True, "serve_phrase_docs")])
def test_device_programs_are_named_after_what_they_serve(
        servers, kind, topk, doclist, name):
    server = servers[PHRASE if kind == PHRASE else AND]
    qt = np.zeros((4, 2), np.int32)
    ql = np.full(4, 2, np.int32)
    cursor, _ = next(server._windows(qt, ql, np.ones(4, bool)))
    traces = server.trace_count
    lowered = server._step(kind, 2, topk=topk, doclist=doclist).lower(
        server.arrays, qt, ql, *cursor)
    assert f"module @jit_{name} " in lowered.as_text()
    assert server.trace_count == traces + 1  # the retrace counter still counts


def test_the_and_step_carries_its_scopes(session):
    server = session.server
    assert server.layout == "fused"
    qt = np.zeros((4, 2), np.int32)
    ql = np.full(4, 2, np.int32)
    cursor, _ = next(server._windows(qt, ql, np.ones(4, bool)))
    hlo = server._step(AND, 2).lower(server.arrays, qt, ql,
                                     *cursor).compile().as_text()
    assert hlo.startswith("HloModule jit_serve_and")
    # op names read "jit(serve_and)/probe/vmap(anchor_search)/while/..."
    scopes = {part.split("(")[-1].rstrip(")")
              for name in re.findall(r'op_name="([^"]+)"', hlo)
              for part in name.split("/")}
    assert {"candidates", "probe", "anchor_search", "pool_search"} <= scopes
