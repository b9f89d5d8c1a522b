"""Cross-backend differential fuzz suite.

With 24 registered backends behind one protocol, the main correctness risk
is *drift*: one backend answering a query differently from the rest.  This
suite builds randomized versioned collections over a range of mutation
rates — including the degenerate 0% (all versions identical: maximal
repetitiveness) and 100% (every word position mutated) — and asserts every
registered backend returns byte-identical word / AND / phrase / topk /
docs / docs-topk answers vs a brute-force NumPy reference, through the same
``Session`` API.

Reproduction: every assertion message carries the ``(seed, edit_rate,
store, query)`` tuple that produced it; the base seed can be pinned with
``REPRO_DIFF_SEED`` (the CI script fixes it), so a failure shrinks to a
one-liner: rebuild the named collection and replay the named query.
"""

import os

import numpy as np
import pytest

from repro.core.artifact import open_index, save_index
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.core.registry import backend_names
from repro.core.writer import IndexWriter
from repro.serving.engine import BatchedServer
from repro.serving.plan import parse_query
from repro.serving.session import Session

from refcase import RefCase, as_reference

BASE_SEED = int(os.environ.get("REPRO_DIFF_SEED", "20260727"))
EDIT_RATES = (0.0, 0.2, 1.0)  # none / moderate / total mutation
ALL_BACKENDS = backend_names()

# one backend per family for the cross-family agreement check:
# run-length (rice_runs), LZ (vbyte_lzend), grammar (repair_skip),
# self-index (rlcsa), referential (rlz — mined-cluster heads)
FAMILY_REPS = ("rice_runs", "vbyte_lzend", "repair_skip", "rlcsa", "rlz")


@pytest.fixture(scope="module", params=EDIT_RATES, ids=lambda r: f"rate={r}")
def case(request) -> RefCase:
    rate = request.param
    return RefCase(rate, BASE_SEED + EDIT_RATES.index(rate))


@pytest.fixture(scope="module")
def rt_case() -> RefCase:
    """One moderate-mutation case for the artifact/lifecycle identities
    (the per-rate sweep above already covers query semantics)."""
    return RefCase(0.2, BASE_SEED + EDIT_RATES.index(0.2))


# ----------------------------------------------------------------------
# every backend vs the reference, all query kinds
# ----------------------------------------------------------------------
@pytest.mark.parametrize("store", ALL_BACKENDS)
def test_backend_matches_reference(case, store):
    idx = NonPositionalIndex.build(case.docs, store=store)
    pidx = PositionalIndex.build(case.docs, store=store)
    session = Session(idx, positional=pidx)
    rng = np.random.default_rng(case.seed + 1)
    for q, ref in case.sample_queries(rng):
        got = as_reference(q, session.execute(q))
        assert got.dtype == ref.dtype and np.array_equal(got, ref), (
            f"differential mismatch: seed={case.seed} edit_rate={case.rate} "
            f"store={store!r} query={q!r} got={got.tolist()} "
            f"want={ref.tolist()}")


# ----------------------------------------------------------------------
# cross-family byte-identity + device/host doc-listing agreement
# ----------------------------------------------------------------------
def test_doc_listing_identical_across_families(case):
    """Acceptance: docs / docs-topk answers agree byte-for-byte across the
    run-length, LZ, grammar, and self-index families."""
    sessions = {}
    for store in FAMILY_REPS:
        sessions[store] = Session(
            NonPositionalIndex.build(case.docs, store=store),
            positional=PositionalIndex.build(case.docs, store=store))
    rng = np.random.default_rng(case.seed + 2)
    queries = [q for q, _ in case.sample_queries(rng)
               if parse_query(q).kind in ("docs", "docs_topk")]
    base = FAMILY_REPS[0]
    for q in queries:
        want = np.asarray(sessions[base].execute(q))
        for store in FAMILY_REPS[1:]:
            got = np.asarray(sessions[store].execute(q))
            assert got.dtype == want.dtype and np.array_equal(got, want), (
                f"family drift: seed={case.seed} edit_rate={case.rate} "
                f"query={q!r} {base}={want.tolist()} {store}={got.tolist()}")


# ----------------------------------------------------------------------
# index lifecycle identities: persisted artifacts and segmented ingestion
# answer byte-identically to the in-memory one-shot build
# ----------------------------------------------------------------------
@pytest.mark.parametrize("store", ALL_BACKENDS)
def test_artifact_roundtrip_matches_reference(rt_case, store, tmp_path):
    """Acceptance: for every registered backend,
    ``open_index(save_index(build(...)))`` answers all six query kinds
    byte-identically to the brute-force reference."""
    case = rt_case
    idx = open_index(save_index(
        NonPositionalIndex.build(case.docs, store=store), tmp_path / "np"))
    pidx = open_index(save_index(
        PositionalIndex.build(case.docs, store=store), tmp_path / "pos"))
    session = Session(idx, positional=pidx)
    rng = np.random.default_rng(case.seed + 4)
    for q, ref in case.sample_queries(rng):
        got = as_reference(q, session.execute(q))
        assert got.dtype == ref.dtype and np.array_equal(got, ref), (
            f"artifact round-trip mismatch: seed={case.seed} "
            f"edit_rate={case.rate} store={store!r} query={q!r} "
            f"got={got.tolist()} want={ref.tolist()}")


@pytest.mark.parametrize("store", FAMILY_REPS)
def test_writer_three_commits_matches_one_shot(rt_case, store, tmp_path):
    """Acceptance: a 3-commit ``IndexWriter`` ingest served segment-aware
    through ``Session.open`` — and again after ``compact()`` — answers
    every query kind byte-identically to a fresh one-shot build."""
    case = rt_case
    writer = IndexWriter(tmp_path / "ix", store=store, positional=True)
    cuts = (0, 3, 6, len(case.docs))
    for lo, hi in zip(cuts, cuts[1:]):
        writer.add_documents(case.docs[lo:hi])
        writer.commit()
    assert len(writer.segments) == 3
    one_shot = Session(NonPositionalIndex.build(case.docs, store=store),
                       positional=PositionalIndex.build(case.docs, store=store))
    rng = np.random.default_rng(case.seed + 5)
    queries = [q for q, _ in case.sample_queries(rng)]
    want = [np.asarray(r) for r in one_shot.execute(queries)]

    segmented = Session.open(tmp_path / "ix", device=False)
    for q, w, g in zip(queries, want, segmented.execute(queries)):
        g = np.asarray(g)
        assert g.dtype == w.dtype and np.array_equal(g, w), (
            f"segmented/one-shot drift: seed={case.seed} "
            f"edit_rate={case.rate} store={store!r} query={q!r} "
            f"segmented={g.tolist()} one_shot={w.tolist()}")

    writer.compact()
    assert len(writer.segments) == 1
    assert segmented.refresh() == 1  # compaction reopens the merged segment
    for q, w, g in zip(queries, want, segmented.execute(queries)):
        assert np.array_equal(np.asarray(g), w), (
            f"compacted/one-shot drift: seed={case.seed} "
            f"edit_rate={case.rate} store={store!r} query={q!r} "
            f"compacted={np.asarray(g).tolist()} one_shot={w.tolist()}")


def test_device_rank_matches_host(case):
    """The dense device BM25 path (scatter-add + ``lax.top_k``, float32)
    returns exactly the host MaxScore answers — and the brute reference."""
    idx = NonPositionalIndex.build(case.docs, store="repair_skip")
    dev = Session.build(idx, device=True)
    host = Session.build(idx, device=False)
    rng = np.random.default_rng(case.seed + 6)
    queries = [q for q, _ in case.sample_queries(rng)
               if parse_query(q).kind == "rank"]
    refs = dict(case.sample_queries(np.random.default_rng(case.seed + 6)))
    plans = [dev.plan(q) for q in queries]
    assert any(p.route == "device" for p in plans), queries
    for q, g in zip(queries, dev.execute(queries)):
        h = np.asarray(host.execute(q))
        assert np.array_equal(np.asarray(g), h), (
            f"device/host rank drift: seed={case.seed} edit_rate={case.rate} "
            f"query={q!r} device={np.asarray(g).tolist()} host={h.tolist()}")
        assert np.array_equal(h, refs[q]), (
            f"rank reference mismatch: seed={case.seed} "
            f"edit_rate={case.rate} query={q!r} got={h.tolist()} "
            f"want={refs[q].tolist()}")


def test_fused_layout_matches_host(case):
    """The fused device layout (compressed postings in HBM, decode inside
    the sweep) returns byte-identical answers to the host route for every
    query kind, at every edit rate — and matches the dense layout."""
    idx = NonPositionalIndex.build(case.docs, store="repair_skip")
    pidx = PositionalIndex.build(case.docs, store="repair_skip")
    fused = Session.build(idx, positional=pidx, layout="fused")
    dense = Session.build(idx, positional=pidx, layout="dense")
    host = Session.build(idx, positional=pidx, device=False)
    rng = np.random.default_rng(case.seed + 11)
    for q, ref in case.sample_queries(rng):
        g = np.asarray(fused.execute(q))
        h = np.asarray(host.execute(q))
        d = np.asarray(dense.execute(q))
        assert np.array_equal(g, h), (
            f"fused/host drift: seed={case.seed} edit_rate={case.rate} "
            f"query={q!r} fused={g.tolist()} host={h.tolist()}")
        assert np.array_equal(g, d), (
            f"fused/dense drift: seed={case.seed} edit_rate={case.rate} "
            f"query={q!r} fused={g.tolist()} dense={d.tolist()}")


def test_device_doclist_matches_host(case):
    """The batched device listing path (segment-max dedup inside the
    windowed sweep) returns exactly the host answers."""
    idx = NonPositionalIndex.build(case.docs, store="repair_skip")
    pidx = PositionalIndex.build(case.docs, store="repair_skip")
    dev = Session(idx, positional=pidx,
                  server=BatchedServer.from_index(idx),
                  positional_server=BatchedServer.from_index(pidx))
    host = Session(idx, positional=pidx)
    rng = np.random.default_rng(case.seed + 3)
    queries = [q for q, _ in case.sample_queries(rng)
               if parse_query(q).kind == "docs"]
    plans = [dev.plan(q) for q in queries]
    assert any(p.route == "device" for p in plans), queries
    got = dev.execute(queries)
    for q, g in zip(queries, got):
        h = np.asarray(host.execute(q))
        assert np.array_equal(np.asarray(g), h), (
            f"device/host drift: seed={case.seed} edit_rate={case.rate} "
            f"query={q!r} device={np.asarray(g).tolist()} host={h.tolist()}")
