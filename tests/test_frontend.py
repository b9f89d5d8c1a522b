"""Serving-frontier lockdown: differential correctness under concurrency.

The micro-batch frontend reorders, coalesces, caches, and replicates
traffic — none of which may change a single answer.  These tests drive
randomized concurrent arrival orders, interleaved query kinds, and burst
traffic through :class:`~repro.serving.frontend.MicroBatchFrontend` and
assert the results are **byte-identical** to direct ``Session.execute()``
on the same queries (the ``test_differential`` brute-reference pattern:
one backend per family, seeds in every failure message), plus the fault
surface: typed queue-full rejection, deadline-triggered straggler flush,
and whole-batch replica failover.
"""

import asyncio
import os

import numpy as np
import pytest

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.queries import sample_traffic
from repro.serving.frontend import (
    AllReplicasFailed,
    FrontendClosed,
    FrontendConfig,
    FrontendError,
    FrontendOverloaded,
    MicroBatchFrontend,
    ReplicatedServer,
    replicated_session,
    run_open_loop,
)
from repro.serving.session import Session

BASE_SEED = int(os.environ.get("REPRO_DIFF_SEED", "20260727"))

# one backend per family (run-length / LZ / grammar / self-index) — the
# cross-family pattern of tests/test_differential.py
FAMILY_REPS = ("rice_runs", "vbyte_lzend", "repair_skip", "rlcsa")


@pytest.fixture(scope="module")
def collection():
    return generate_collection(n_articles=2, versions_per_article=4,
                               words_per_doc=45, seed=BASE_SEED % 10_000)


@pytest.fixture(scope="module", params=FAMILY_REPS)
def family_case(request, collection):
    """(store, device session, host reference session) per backend family."""
    store = request.param
    idx = NonPositionalIndex.build(collection.docs, store=store)
    pidx = PositionalIndex.build(collection.docs, store=store)
    return store, Session.build(idx, positional=pidx), Session(idx, positional=pidx)


@pytest.fixture(scope="module")
def vbyte_case(collection):
    """A cheap inverted backend for the scheduler/fault tests."""
    idx = NonPositionalIndex.build(collection.docs, store="vbyte")
    pidx = PositionalIndex.build(collection.docs, store="vbyte")
    return idx, pidx


def mixed_queries(collection, session, rng, n=24):
    """All query kinds, sampled from the collection (duplicates likely)."""
    words = [w for w in session.primary_index.vocab.id_to_token[:60]]
    out = sample_traffic("mixed", n - 4, collection.docs, words, rng)
    out += [f"docs-top3: {words[0]} {words[1]}", f"top3: {words[0]} {words[1]}",
            f"top5: {words[0]} {words[1]}", "docs: qqqzz unknownzz"]
    return out


def drive_concurrent(session, queries, seed, config=None):
    """Submit ``queries`` in a random arrival order with random delays;
    results come back indexed by original position."""
    rng = np.random.default_rng(seed)
    config = config or FrontendConfig(max_batch=8, max_delay=0.002)

    async def main():
        async with MicroBatchFrontend(session, config) as fe:
            results = [None] * len(queries)

            async def one(i: int) -> None:
                await asyncio.sleep(float(rng.random()) * 0.004)
                results[i] = await fe.submit(queries[i])

            order = [int(i) for i in rng.permutation(len(queries))]
            await asyncio.gather(*(one(i) for i in order))
            return results, fe.metrics()

    return asyncio.run(main())


# ----------------------------------------------------------------------
# differential correctness under concurrency (>= 4 backend families)
# ----------------------------------------------------------------------
def test_frontend_differential_concurrent(family_case, collection):
    store, session, host = family_case
    for round_ in range(3):
        seed = BASE_SEED + 31 * round_
        rng = np.random.default_rng(seed)
        queries = mixed_queries(collection, session, rng)
        reference = host.execute(queries)
        got, metrics = drive_concurrent(session, queries, seed)
        for q, ref, res in zip(queries, reference, got):
            assert res is not None, \
                f"(seed={seed}, store={store}, query={q!r}): no result"
            assert np.array_equal(np.asarray(ref), np.asarray(res)), \
                (f"(seed={seed}, store={store}, query={q!r}): frontend "
                 f"{np.asarray(res)} != direct {np.asarray(ref)}")
        assert metrics["rejected"] == 0
        assert metrics["batches"] >= 1


def test_frontend_burst_traffic(family_case, collection):
    """Everything submitted at once: size-triggered flushes, same answers."""
    store, session, host = family_case
    seed = BASE_SEED + 7
    rng = np.random.default_rng(seed)
    queries = mixed_queries(collection, session, rng, n=32)
    reference = host.execute(queries)

    async def main():
        async with MicroBatchFrontend(
                session, FrontendConfig(max_batch=4, max_delay=0.05)) as fe:
            results = await asyncio.gather(*(fe.submit(q) for q in queries))
            return results, fe.metrics()

    got, metrics = asyncio.run(main())
    for q, ref, res in zip(queries, reference, got):
        assert np.array_equal(np.asarray(ref), np.asarray(res)), \
            (f"(seed={seed}, store={store}, query={q!r}): burst result "
             f"{np.asarray(res)} != direct {np.asarray(ref)}")
    assert metrics["flushes"]["size"] >= 1, metrics


# ----------------------------------------------------------------------
# scheduler behavior: deadline straggler, size trigger, queue bound
# ----------------------------------------------------------------------
def test_deadline_flush_single_straggler(vbyte_case):
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)
    host = Session(idx, positional=pidx)
    w = idx.vocab.id_to_token[1]
    q = f"{w} {idx.vocab.id_to_token[2]}"

    async def main():
        async with MicroBatchFrontend(
                session, FrontendConfig(max_batch=64, max_delay=0.01)) as fe:
            res = await fe.submit(q)  # nothing else arrives: deadline fires
            return res, fe.metrics()

    res, metrics = asyncio.run(main())
    assert np.array_equal(np.asarray(res), host.execute(q))
    assert metrics["flushes"]["deadline"] == 1, metrics
    assert metrics["flushes"]["size"] == 0, metrics


def test_size_trigger_fills_bucket(vbyte_case):
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)
    words = idx.vocab.id_to_token
    queries = [f"{words[i]} {words[i + 1]}" for i in range(1, 9)]

    async def main():
        async with MicroBatchFrontend(
                session, FrontendConfig(max_batch=8, max_delay=5.0)) as fe:
            results = await asyncio.gather(*(fe.submit(q) for q in queries))
            return results, fe.metrics()

    results, metrics = asyncio.run(main())
    assert all(r is not None for r in results)
    # the deadline was 5s: only the size trigger can have flushed
    assert metrics["flushes"]["size"] == 1, metrics
    assert metrics["flushes"]["deadline"] == 0, metrics
    assert metrics["max_batch"] == 8, metrics


def test_queue_full_typed_rejection(vbyte_case):
    """Admission control rejects immediately with a typed error — no hang."""
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)
    words = idx.vocab.id_to_token
    config = FrontendConfig(max_batch=100, max_delay=5.0, max_pending=4)

    async def main():
        async with MicroBatchFrontend(session, config) as fe:
            tasks = [asyncio.ensure_future(
                fe.submit(f"{words[i]} {words[i + 1]}")) for i in range(1, 5)]
            await asyncio.sleep(0)  # let the four submissions enqueue
            assert fe.depth == 4
            with pytest.raises(FrontendOverloaded) as err:
                await fe.submit(f"{words[9]} {words[10]}")
            assert err.value.pending == 4
            assert err.value.limit == 4
            assert isinstance(err.value, FrontendError)
            assert fe.metrics()["rejected"] == 1
            # draining completes the queued four without waiting out the
            # 5s deadline — rejection sheds load, it never cancels work
            await fe.drain()
            results = await asyncio.gather(*tasks)
            assert all(len(np.asarray(r).shape) == 1 for r in results)

    asyncio.run(main())


def test_closed_frontend_rejects(vbyte_case):
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)

    async def main():
        fe = MicroBatchFrontend(session, FrontendConfig())
        await fe.close()
        with pytest.raises(FrontendClosed):
            await fe.submit(idx.vocab.id_to_token[1])

    asyncio.run(main())


# ----------------------------------------------------------------------
# replica fan-out: least-loaded dispatch, mid-batch failover
# ----------------------------------------------------------------------
def test_replicated_differential(vbyte_case, collection):
    """N replicas x M shards answers == plain host session answers."""
    idx, pidx = vbyte_case
    host = Session(idx, positional=pidx)
    rng = np.random.default_rng(BASE_SEED + 5)
    words = idx.vocab.id_to_token[:40]
    queries = (sample_traffic("and", 8, collection.docs, words, rng)
               + sample_traffic("phrase", 8, collection.docs, words, rng))
    session = replicated_session(idx, positional=pidx, n_replicas=2, n_shards=2)
    reference = host.execute(queries)
    got = session.execute(queries)
    for q, ref, res in zip(queries, reference, got):
        assert np.array_equal(np.asarray(ref), np.asarray(res)), \
            f"(store=vbyte, query={q!r}): replicated != host"
    assert session.server.batches_dispatched >= 1
    assert all(r["healthy"] for r in session.server.replica_status())


def test_replica_failover_mid_batch(vbyte_case):
    """A replica raising mid-batch fails over: the whole bucket is
    re-dispatched, no query dropped, the bad replica marked unhealthy."""
    idx, pidx = vbyte_case
    host = Session(idx, positional=pidx)
    words = idx.vocab.id_to_token
    queries = [f"{words[i]} {words[i + 1]}" for i in range(1, 7)]
    rs = ReplicatedServer.build(idx, n_replicas=2)

    victim = rs._replicas[0].server
    original = victim.conjunctive
    calls = {"n": 0}

    def exploding(queries, width=None):
        calls["n"] += 1
        raise RuntimeError("replica wedged mid-batch")

    victim.conjunctive = exploding
    session = Session(idx, server=rs)
    got = session.execute(queries)
    reference = host.execute(queries)
    for q, ref, res in zip(queries, reference, got):
        assert np.array_equal(np.asarray(ref), np.asarray(res)), \
            f"query={q!r}: failover dropped or corrupted the answer"
    assert calls["n"] == 1
    assert rs.failovers == 1
    status = rs.replica_status()
    assert [r["healthy"] for r in status] == [False, True], status
    assert status[1]["served"] == len(queries)
    victim.conjunctive = original


def test_replica_failover_mid_rank_batch(vbyte_case):
    """``rank<k>:`` rides the same dispatch: a replica raising in
    ``ranked`` fails over and the BM25 answers equal the host's."""
    idx, _ = vbyte_case
    words = idx.vocab.id_to_token
    queries = [f"rank5: {words[i]} {words[i + 1]}" for i in range(1, 5)]
    rs = ReplicatedServer.build(idx, n_replicas=2)
    assert "rank" in rs.kinds

    def exploding(queries, k=10, width=None):
        raise RuntimeError("replica wedged mid-batch")

    rs._replicas[0].server.ranked = exploding
    session = Session(idx, server=rs)
    assert all(session.plan(q).route == "device" for q in queries)
    got = session.execute(queries)
    for q, ref, res in zip(queries, Session(idx).execute(queries), got):
        assert np.array_equal(np.asarray(ref), np.asarray(res)), q
    assert rs.failovers == 1
    assert rs.replica_status()[1]["served"] == len(queries)


def test_all_replicas_failed_is_typed(vbyte_case):
    idx, pidx = vbyte_case
    rs = ReplicatedServer.build(idx, n_replicas=2)
    for rep in rs._replicas:
        rep.server.conjunctive = lambda queries, width=None: (_ for _ in ()).throw(
            RuntimeError("down"))
    session = Session(idx, server=rs)
    words = idx.vocab.id_to_token
    with pytest.raises(AllReplicasFailed):
        session.execute(f"{words[1]} {words[2]}")

    # ... and through the frontend the typed error reaches the submitter
    async def main():
        async with MicroBatchFrontend(session, FrontendConfig(
                max_delay=0.001)) as fe:
            with pytest.raises(AllReplicasFailed):
                await fe.submit(f"{words[3]} {words[4]}")

    asyncio.run(main())


# ----------------------------------------------------------------------
# metrics surface + open-loop driver
# ----------------------------------------------------------------------
def test_latency_metrics_through_session(vbyte_case, collection):
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)
    rng = np.random.default_rng(BASE_SEED + 11)
    queries = mixed_queries(collection, session, rng, n=12)
    results, report = run_open_loop(session, queries, rate_qps=0.0,
                                    config=FrontendConfig(max_batch=4))
    assert all(r is not None for r in results)
    assert report["rejected"] == 0
    for key in ("p50_ms", "p95_ms", "p99_ms", "queue_depth_max"):
        assert key in report["latency"], report

    # an attached frontend surfaces through Session.metrics()
    async def main():
        async with MicroBatchFrontend(session, FrontendConfig()) as fe:
            await fe.submit(queries[0])
            return session.metrics()

    m = asyncio.run(main())
    assert m["frontend"]["submitted"] == 1
    assert m["frontend"]["latency"]["count"] == 1
    assert "queue_depth_max" in m["frontend"]["latency"]


def test_refresh_threadsafe_without_loop_falls_back_inline(tmp_path):
    """Before any traffic has touched the event loop, the compaction
    on_swap hook must still work: refresh_threadsafe degrades to an
    inline Session.refresh."""
    from repro.core.writer import IndexWriter

    w = IndexWriter(tmp_path / "col", store="vbyte", positional=True)
    w.add_documents(["alpha beta gamma", "beta delta alpha"])
    w.commit()
    session = Session.open(w.path, device=False)
    fe = MicroBatchFrontend(session, FrontendConfig())
    handle = w.compact_async(on_swap=fe.refresh_threadsafe)
    handle.wait(60)
    assert len(session._segments) == 1
    assert np.array_equal(np.asarray(session.execute("docs: alpha")),
                          np.asarray([0, 1]))


def test_mid_flight_refresh_never_caches_across_shapes(tmp_path):
    """A batch whose execution straddles a refresh must not deposit its
    answers under the new segment shape (the p.key guard): afterwards the
    cache serves only answers computed against the live shape."""
    from repro.core.writer import IndexWriter

    w = IndexWriter(tmp_path / "col", store="vbyte", positional=True)
    w.add_documents(["alpha beta gamma", "beta delta alpha"])
    w.commit()
    session = Session.open(w.path, device=False)
    orig_execute = session.execute
    w.add_documents(["alpha zebra quartz"])
    w.commit()

    def refresh_mid_batch(queries):
        out = orig_execute(queries)
        session.refresh()  # the shape moves while the batch is in flight
        return out

    session.execute = refresh_mid_batch

    async def main():
        fe = MicroBatchFrontend(session,
                                FrontendConfig(max_batch=4, max_delay=0.001))
        stale = np.asarray(await fe.submit("docs: alpha"))
        session.execute = orig_execute
        fresh = np.asarray(await fe.submit("docs: alpha"))
        metrics = fe.cache.metrics()
        await fe.close()
        return stale, fresh, metrics

    stale, fresh, metrics = asyncio.run(main())
    assert np.array_equal(stale, np.asarray([0, 1]))  # pre-refresh snapshot
    assert np.array_equal(fresh, np.asarray([0, 1, 2]))  # live shape
    # the straddling answer was served but never cached: the second submit
    # was a miss, not a stale hit
    assert metrics["hits"] == 0, metrics


def test_open_loop_overload_rejects_not_hangs(vbyte_case, collection):
    """At an absurd offered load over a tiny queue the driver must come
    back with rejections recorded, not deadlock."""
    idx, pidx = vbyte_case
    session = Session.build(idx, positional=pidx)
    rng = np.random.default_rng(BASE_SEED + 13)
    queries = mixed_queries(collection, session, rng, n=40)
    config = FrontendConfig(max_batch=4, max_delay=0.5, max_pending=2)
    results, report = run_open_loop(session, queries, rate_qps=0.0,
                                    config=config)
    assert report["rejected"] > 0
    assert report["rejected"] == sum(1 for r in results if r is None)
    served = [i for i, r in enumerate(results) if r is not None]
    host = Session(idx, positional=pidx)
    reference = host.execute([queries[i] for i in served])
    for i, ref in zip(served, reference):
        assert np.array_equal(np.asarray(results[i]), np.asarray(ref))
