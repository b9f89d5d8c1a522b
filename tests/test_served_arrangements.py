"""Device serving across the ways a collection is deployed.

Each case answers one query kind through one device arrangement and
compares the answers with the brute-force NumPy reference of
``refcase.RefCase``:

* ``artifact`` — ``Session.open`` of a persisted index bundle (the path
  the benchmark serves);
* ``segments`` — ``Session.open`` of a 3-commit ``IndexWriter`` directory,
  served segment by segment;
* ``compacted`` — the same after ``compact()`` and ``refresh()``;
* ``replicas`` — ``replicated_session(n_replicas=2, n_shards=1)``;
* ``sharded-replicas`` — ``replicated_session(n_replicas=2, n_shards=2)``.

over ``repair_skip`` (device resident, served fused) and ``vbyte`` (served
on the dense layout).  Every case asserts that its session holds device
servers, and that the kinds its arrangement serves on the device did reach
the device, so no case passes on the host by accident.

Left out, because ``test_frontend.py::test_replicated_differential``
already checks them: ``sharded-replicas`` x ``vbyte`` x ``and`` /
``phrase``.
"""

import os

import numpy as np
import pytest

from repro.core.artifact import save_index
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.core.writer import IndexWriter
from repro.serving.frontend import replicated_session
from repro.serving.plan import parse_query
from repro.serving.session import Session

from refcase import RefCase, as_reference

BASE_SEED = int(os.environ.get("REPRO_DIFF_SEED", "20260727"))
STORES = ("repair_skip", "vbyte")
KINDS = ("word", "and", "phrase", "topk", "docs", "docs_topk", "rank")
#: the kinds each arrangement answers on the device; the others it answers
#: on the host by design (a word is one list decode, ``docs-top<k>`` needs
#: the host tf structure, segments merge BM25 scores on the host, and the
#: partitioned server runs AND and phrase only)
DEVICE_KINDS = {
    "artifact": {"and", "phrase", "topk", "docs", "rank"},
    "segments": {"and", "phrase", "topk", "docs"},
    "compacted": {"and", "phrase", "topk", "docs"},
    "replicas": {"and", "phrase", "topk", "docs", "rank"},
    "sharded-replicas": {"and", "phrase"},
}
COVERED = {("sharded-replicas", "vbyte", "and"),
           ("sharded-replicas", "vbyte", "phrase")}
CASES = [(a, s, k) for a in DEVICE_KINDS for s in STORES for k in KINDS
         if (a, s, k) not in COVERED]


@pytest.fixture(scope="module")
def case() -> RefCase:
    return RefCase(0.2, BASE_SEED + 1)


@pytest.fixture(scope="module")
def queries(case) -> dict[str, list[tuple[str, np.ndarray]]]:
    """The reference's sampled queries, grouped by kind."""
    by_kind: dict[str, list] = {k: [] for k in KINDS}
    for draw in range(2):
        rng = np.random.default_rng(case.seed + 20 + draw)
        for q, ref in case.sample_queries(rng):
            by_kind[parse_query(q).kind].append((q, ref))
    return by_kind


def _writer(case, store, path) -> IndexWriter:
    writer = IndexWriter(path, store=store, positional=True)
    cuts = (0, 3, 6, len(case.docs))
    for lo, hi in zip(cuts, cuts[1:]):
        writer.add_documents(case.docs[lo:hi])
        writer.commit()
    assert len(writer.segments) == 3
    return writer


def _open(arrangement, case, store, path) -> Session:
    if arrangement == "segments":
        _writer(case, store, path)
        return Session.open(path)
    if arrangement == "compacted":
        writer = _writer(case, store, path)
        session = Session.open(path)
        writer.compact()
        assert session.refresh() == 1
        return session
    idx = NonPositionalIndex.build(case.docs, store=store)
    pidx = PositionalIndex.build(case.docs, store=store)
    if arrangement == "artifact":
        save_index(idx, path / "nonpositional")
        save_index(pidx, path / "positional")
        return Session.open(path)
    n_shards = 2 if arrangement == "sharded-replicas" else 1
    return replicated_session(idx, positional=pidx, n_replicas=2,
                              n_shards=n_shards)


@pytest.fixture(scope="module")
def served(case, tmp_path_factory):
    """``served(arrangement, store)``: the session, built once per module."""
    sessions: dict[tuple[str, str], Session] = {}

    def get(arrangement: str, store: str) -> Session:
        key = (arrangement, store)
        if key not in sessions:
            path = tmp_path_factory.mktemp(f"{arrangement}-{store}")
            sessions[key] = _open(arrangement, case, store, path)
        return sessions[key]

    return get


def _serving(session: Session) -> list[Session]:
    """The sessions that hold device servers: a segmented session's
    per-segment children, else the session itself."""
    return [s.session for s in session._segments] or [session]


@pytest.mark.parametrize("arrangement,store,kind", CASES)
def test_device_arrangement_matches_reference(served, queries, arrangement,
                                              store, kind):
    session = served(arrangement, store)
    children = _serving(session)
    assert all(c.server is not None and c.positional_server is not None
               for c in children), "no device server attached"
    before = sum(c.device_batches for c in children)
    for q, ref in queries[kind]:
        got = as_reference(q, session.execute(q))
        assert got.dtype == ref.dtype and np.array_equal(got, ref), (
            f"{arrangement}/{store} query={q!r} got={got.tolist()} "
            f"want={ref.tolist()}")
    if kind in DEVICE_KINDS[arrangement]:
        assert sum(c.device_batches for c in children) > before, (
            f"{arrangement}/{store}: no {kind} query reached the device")
