"""Windowed-sweep boundary regression guard (the PR-1 truncation bug class).

The batched device path sweeps windows over the driving list until it is
exhausted.  On the dense layout a window is MAX_CAND_ROWS C-entries; on
the fused layout it is budgeted in postings: each query takes the
C-entries whose postings fit ``window_lanes(max_phrase)`` lanes (``Cq``).
Off-by-one bugs in either sweep bite exactly at the window size, so these
tests pin driving lists at MAX_CAND_ROWS ± 1 and 3 * MAX_CAND_ROWS
C-entries, and at Cq ± 1 and 3 * Cq postings, and require device results
identical to the host engine.  `max_rules=0` disables grammar rounds so
every posting is one C-entry — list length == C-entry count,
deterministically; the grammar-compressed variant holds an entry of
exactly ``max_phrase`` postings.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.anchors import build_anchored
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.serving.engine import (
    MAX_CAND_ROWS,
    BatchedServer,
    QueryEngine,
    make_serve_step,
    window_lanes,
)
from repro.serving.session import Session

from lane_budget import lane_budget_windows

BOUNDARY_LENGTHS = (MAX_CAND_ROWS - 1, MAX_CAND_ROWS, MAX_CAND_ROWS + 1,
                    3 * MAX_CAND_ROWS)
N_DOCS = 3 * MAX_CAND_ROWS + 8


@pytest.fixture(scope="module")
def boundary_index():
    """A collection where word ``w<L>`` occurs in exactly docs [0, L) and
    ``common`` in every doc, indexed with ``max_rules=0`` so posting-list
    length equals C-entry count exactly; one server per layout."""
    docs = []
    for d in range(N_DOCS):
        words = ["common"] + [f"w{L}" for L in BOUNDARY_LENGTHS if d < L]
        docs.append(" ".join(words))
    idx = NonPositionalIndex.build(docs, store="repair", max_rules=0)
    servers = {layout: BatchedServer.from_index(idx, layout=layout)
               for layout in ("dense", "fused")}
    return idx, servers


@pytest.mark.parametrize("length", BOUNDARY_LENGTHS)
def test_and_at_window_boundaries(boundary_index, length):
    idx, servers = boundary_index
    wid = idx.word_id(f"w{length}")
    host = QueryEngine(idx)
    q = [f"w{length}", "common"]
    want = np.asarray(host.conjunctive(q))
    assert len(want) == length  # w<L> ∩ all-docs == [0, L)
    for layout, server in servers.items():
        c_off = np.asarray(server.arrays["c_offsets"])
        assert int(c_off[wid + 1] - c_off[wid]) == length, \
            "C-entries must equal list length"
        dev = server.conjunctive([q])[0]
        assert np.array_equal(dev, want), (layout, length, len(dev))
    # the dense sweep runs exactly ceil(L / MAX_CAND_ROWS) windows; the
    # fused one as many as the lane budget packs (one here: L < Cq)
    qt, ql, ok = servers["dense"].encode([q], sort_by_length=True)
    assert servers["dense"]._n_windows(qt, ql, ok) == -(-length // MAX_CAND_ROWS)
    fused = servers["fused"]
    assert fused._n_windows(qt, ql, ok) == len(lane_budget_windows(fused, wid)) == 1


@pytest.mark.parametrize("length", BOUNDARY_LENGTHS)
def test_doclist_at_window_boundaries(boundary_index, length):
    """The device doc-listing dedup must also be window-exact."""
    idx, servers = boundary_index
    host = QueryEngine(idx)
    q = [f"w{length}", "common"]
    want = host.doc_list(q)
    for layout, server in servers.items():
        dev = server.doclist([q])[0]
        assert np.array_equal(dev, want), (layout, length, len(dev))


def test_phrase_step_at_exact_window_multiple():
    """Anchored phrase probing where the driving list is an exact multiple
    of the window (no partial final window to hide truncation)."""
    n = 4 * MAX_CAND_ROWS
    a = (np.arange(n, dtype=np.int64) * 3)          # len == 4 * window
    b = a[::2] + 1                                  # phrase partner
    aidx = build_anchored([a, b], max_rules=0)
    c_off = np.asarray(aidx.c_offsets)
    assert int(c_off[1] - c_off[0]) == n
    arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
              "expand": aidx.expand, "expand_valid": aidx.expand_valid,
              "lengths": aidx.lengths}
    step = jax.jit(make_serve_step(max_terms=2, mode="phrase"))
    qt = jnp.asarray([[0, 1]], jnp.int32)
    ql = jnp.asarray([2], jnp.int32)
    hits = []
    for w in range(-(-n // MAX_CAND_ROWS)):
        vals, mask = step(arrays, qt, ql, w * MAX_CAND_ROWS)
        hits.append(np.asarray(vals)[0][np.asarray(mask)[0]])
    got = np.unique(np.concatenate(hits))
    ref = a[np.isin(a + 1, b)]
    assert np.array_equal(got, ref)
    assert len(ref) == len(b)


# ----------------------------------------------------------------------
# fused layout: windows budgeted in postings
# ----------------------------------------------------------------------
CQ = window_lanes(1)  # the lanes of a max_rules=0 index, whose entries hold 1
LANE_LENGTHS = (CQ - 1, CQ, CQ + 1, 3 * CQ)
LANE_DOCS = 3 * CQ + 8


def _successor(length: int) -> str:
    """The word after ``w<length>`` in every document that holds it."""
    later = [L for L in LANE_LENGTHS if L > length]
    return f"w{later[0]}" if later else "common"


@functools.cache
def _lane_index(grammar: str):
    """Word ``w<L>`` in exactly docs [0, L), ``common`` in every doc and
    ``half`` in the even ones, each doc's ``w`` words in order of L.
    ``postings``: no grammar, every C entry one posting, Cq = 1,024.
    ``rules``: the default grammar, whose longest entry holds exactly
    ``max_phrase`` postings."""
    docs = []
    for d in range(LANE_DOCS):
        words = [f"w{L}" for L in LANE_LENGTHS if d < L] + ["common"]
        docs.append(" ".join(words + (["half"] if d % 2 == 0 else [])))
    kw = {"max_rules": 0} if grammar == "postings" else {}
    idx = NonPositionalIndex.build(docs, store="repair", **kw)
    pidx = PositionalIndex.build(docs, store="repair", **kw)
    session = Session.build(idx, positional=pidx, layout="fused")
    host = Session.build(idx, positional=pidx, device=False)
    return grammar, session, host


@pytest.fixture(scope="module", params=["postings", "rules"])
def lane_index(request):
    return _lane_index(request.param)


def _served(session, host, length: int) -> list[tuple[str, object, object]]:
    """(query, device answer, host answer) of each device-served kind over
    the driving list ``w<length>``."""
    w, succ = f"w{length}", _successor(length)
    server, pserver = session.server, session.positional_server
    cases = [
        (f"{w} common", server.conjunctive([[w, "common"]])[0]),
        (f"docs: {w} common", server.doclist([[w, "common"]])[0]),
        (f"top1000: {w} half", server.topk([[w, "half"]], k=1000)[0]),
        (f'"{w} {succ}"', pserver.phrase([[w, succ]])[0]),
        (f'docs: "{w} {succ}"', pserver.doclist([[w, succ]], phrase=True)[0]),
    ]
    return [(q, dev, host.execute(q)) for q, dev in cases]


@pytest.mark.parametrize("length", LANE_LENGTHS)
def test_fused_sweep_at_lane_budget_boundaries(lane_index, length):
    """AND, ``docs:``, ``top<k>:`` and phrase over driving lists of Cq - 1,
    Cq, Cq + 1 and 3 * Cq postings match the host path, and each sweep
    runs as many windows as the lane budget packs."""
    grammar, session, host = lane_index
    for q, dev, want in _served(session, host, length):
        assert np.array_equal(dev, want), (grammar, q, len(dev), len(want))
        assert len(want) > 0, q
    for server in (session.server, session.positional_server):
        assert server.layout == "fused"
        qt, ql, ok = server.encode([[f"w{length}"]])
        windows = lane_budget_windows(server, int(qt[0, 0]))
        assert server._n_windows(qt, ql, ok) == len(windows)
        if grammar == "postings":  # one posting per entry: ceil(L / Cq)
            assert len(windows) == -(-length // CQ)


def test_fused_entry_of_exactly_max_phrase_postings(lane_index):
    """An entry as long as the longest rule fills its window alone."""
    grammar, session, host = lane_index
    server = session.server
    c_len = np.asarray(server.arrays["c_len"])
    assert int(c_len.max()) == server.max_phrase
    wid = session.index.word_id(f"w{3 * CQ}")
    c_off = np.asarray(server.arrays["c_offsets"])
    entries = c_len[c_off[wid]:c_off[wid + 1]]
    if grammar == "rules":
        assert entries.tolist() == [server.max_phrase]
        assert window_lanes(server.max_phrase) == server.max_phrase == 3 * CQ
    for q, dev, want in _served(session, host, 3 * CQ):
        assert np.array_equal(dev, want), (grammar, q)


def test_fused_batch_whose_queries_finish_in_different_windows():
    """One batch over driving lists of every length, plus a query with an
    unknown word: each query's answer matches the host, and the batch
    sweeps until the longest list is done."""
    grammar, session, host = _lane_index("postings")
    server = session.server
    queries = [[f"w{L}", "common"] for L in LANE_LENGTHS] + [
        ["nosuchword", "common"], ["common", "half"]]
    got = server.conjunctive(queries)
    for q, dev in zip(queries, got):
        assert np.array_equal(dev, host.execute(" ".join(q))), (grammar, q)
    got = server.doclist(queries)
    for q, dev in zip(queries, got):
        assert np.array_equal(dev, host.execute("docs: " + " ".join(q))), q
    qt, ql, ok = server.encode(queries, sort_by_length=True)
    per_query = [len(lane_budget_windows(server, int(qt[i, 0])))
                 for i in range(len(queries)) if ok[i]]
    assert server._n_windows(qt, ql, ok) == max(per_query)
    assert len(set(per_query)) > 1, per_query  # they finish apart
