"""Document listing + ranked document retrieval (core/doclist.py and the
docs:/docs-top<k>: serving paths).

The acceptance bar: listing answers are identical whichever structure
produces them — generic reducer, ILCP-style doc runs, the grammar-aware
phrase-sum walk, a self-index locate, or the batched device dedup."""

import numpy as np
import pytest

from repro.core.doclist import (
    DocRunIndex,
    doc_list_terms,
    grammar_doc_runs,
    positions_to_doc_counts,
    positions_to_docs,
    rank_docs,
)
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.text import tokenize
from repro.serving.engine import BatchedServer
from repro.serving.plan import parse_query
from repro.serving.session import Session


@pytest.fixture(scope="module")
def col():
    return generate_collection(n_articles=3, versions_per_article=8,
                               words_per_doc=80, seed=21)


@pytest.fixture(scope="module")
def pidx(col):
    return PositionalIndex.build(col.docs, store="repair_skip")


# ----------------------------------------------------------------------
# reducers
# ----------------------------------------------------------------------
def test_positions_to_docs_reducer():
    starts = np.asarray([0, 10, 25], dtype=np.int64)
    pos = np.asarray([0, 3, 3, 9, 10, 24, 30], dtype=np.int64)
    assert positions_to_docs(pos, starts).tolist() == [0, 1, 2]
    docs, counts = positions_to_doc_counts(pos, starts)
    assert docs.tolist() == [0, 1, 2] and counts.tolist() == [4, 2, 1]
    # doc_starts=None: inputs are doc ids already, only dedup applies
    assert positions_to_docs(np.asarray([5, 2, 5]), None).tolist() == [2, 5]
    assert positions_to_docs(np.zeros(0, np.int64), starts).size == 0


def test_rank_docs_ties_break_by_doc_id():
    docs = np.asarray([3, 7, 9, 12])
    scores = np.asarray([2, 5, 5, 1])
    assert rank_docs(docs, scores, 3).tolist() == [7, 9, 3]


# ----------------------------------------------------------------------
# grammar walk vs decode+reduce, over every list of a Re-Pair store
# ----------------------------------------------------------------------
def test_grammar_doc_runs_matches_decode(pidx):
    st = pidx.store
    for i in range(st.n_lists):
        gd, gc = grammar_doc_runs(st, i, pidx.doc_starts)
        rd, rc = positions_to_doc_counts(st.get_list(i), pidx.doc_starts)
        assert np.array_equal(gd, rd) and np.array_equal(gc, rc), i


def test_grammar_doc_runs_skips_whole_phrases(pidx):
    """On a repetitive collection the walk must avoid expanding a
    meaningful share of compressed phrases (the point of the fast path)."""
    st = pidx.store
    expanded = 0
    entries = 0
    orig = st.expand_symbol

    def counting(sym):
        nonlocal expanded
        expanded += 1
        return orig(sym)

    st.expand_symbol = counting
    try:
        for i in range(st.n_lists):
            entries += int(st.c_offsets[i + 1] - st.c_offsets[i])
            grammar_doc_runs(st, i, pidx.doc_starts)
    finally:
        st.expand_symbol = orig
    assert expanded < entries, (expanded, entries)


def test_doc_run_index_runs_and_frequencies(col, pidx):
    runs = DocRunIndex(pidx.store, pidx.doc_starts, precompute=True)
    assert runs.size_in_bits > 0
    tok_lists = [tokenize(d) for d in col.docs]
    for t in ("zu", tok_lists[0][0], tok_lists[0][2]):
        tid = pidx.token_id(t)
        if tid is None:
            continue
        want = np.asarray([d for d, toks in enumerate(tok_lists) if t in toks])
        assert np.array_equal(runs.list_docs(tid), want), t
        docs, counts = runs.list_doc_counts(tid)
        assert counts.tolist() == [tok_lists[int(d)].count(t) for d in docs]
        tf = runs.term_frequencies(tid, np.arange(len(col.docs)))
        assert tf.tolist() == [toks.count(t) for toks in tok_lists]
    # conjunction of run docs == set intersection
    a, b = tok_lists[0][0], tok_lists[0][2]
    ids = [pidx.token_id(a), pidx.token_id(b)]
    got = doc_list_terms(runs, ids)
    want = np.intersect1d(runs.list_docs(ids[0]), runs.list_docs(ids[1]))
    assert np.array_equal(got, want)


# ----------------------------------------------------------------------
# query surface + planner strategies
# ----------------------------------------------------------------------
def test_parse_docs_query_forms():
    q = parse_query("docs: a b")
    assert q.kind == "docs" and q.terms == ("a", "b") and not q.phrase
    q = parse_query('docs: "a b"')
    assert q.kind == "docs" and q.terms == ("a", "b") and q.phrase
    q = parse_query("docs-top7: a b")
    assert q.kind == "docs_topk" and q.k == 7 and not q.phrase
    q = parse_query('docs-top2: "a b c"')
    assert q.kind == "docs_topk" and q.k == 2 and q.phrase
    assert parse_query("top3: a b").kind == "topk"  # unchanged


def test_planner_doclist_strategies(col, pidx):
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    toks = tokenize(col.docs[0])[:2]
    sess = Session(idx, positional=pidx)
    assert sess.plan(f"docs: {toks[0]} {toks[1]}").strategy.startswith("doclist+")
    assert sess.plan(f'docs: "{toks[0]}"').strategy == "grammar-doclist"
    assert sess.plan(f'docs: "{toks[0]} {toks[1]}"').strategy == "reduce-doclist"
    si = Session(NonPositionalIndex.build(col.docs[:6], store="rlcsa"),
                 positional=PositionalIndex.build(col.docs[:6], store="rlcsa"))
    assert si.plan(f'docs: "{toks[0]} {toks[1]}"').strategy == "self-doclist"
    # positional-only session: docs queries route to the positional index
    ponly = Session(None, positional=pidx)
    pl = ponly.plan(f"docs: {toks[0]}")
    assert pl.index == "positional" and pl.strategy == "grammar-doclist"
    vb = Session(None, positional=PositionalIndex.build(col.docs[:6], store="vbyte"))
    assert vb.plan(f"docs: {toks[0]}").strategy == "doc-runs"


def test_engine_doclist_paths_agree(col, pidx):
    """Host fast paths and the nonpositional definition give one answer."""
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    sess = Session(idx, positional=pidx)
    ponly = Session(None, positional=pidx)
    words = [w for w in idx.vocab.id_to_token[:8]]
    for w in words[:4]:
        a = sess.execute(f"docs: {w}")
        b = ponly.execute(f"docs: {w}")
        c = positions_to_docs(pidx.query_word(w), pidx.doc_starts)
        assert np.array_equal(a, b) and np.array_equal(a, c), w
    q = f"docs: {words[0]} {words[3]}"
    assert np.array_equal(sess.execute(q), ponly.execute(q))


def test_doc_topk_ranks_by_pattern_frequency(col, pidx):
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    sess = Session(idx, positional=pidx)
    tok_lists = [tokenize(d) for d in col.docs]
    w = [t for t in idx.vocab.id_to_token[:6]]
    q = [w[1], w[4]]
    docs = sess.execute(f"docs: {q[0]} {q[1]}")
    scores = np.asarray([tok_lists[int(d)].count(q[0]) + tok_lists[int(d)].count(q[1])
                         for d in docs])
    want = docs[np.argsort(-scores, kind="stable")][:3]
    got = sess.execute(f"docs-top3: {q[0]} {q[1]}")
    assert np.array_equal(got, want)
    # phrase frequency ranking
    ph = " ".join(tok_lists[0][2:4])
    got = sess.execute(f'docs-top4: "{ph}"')
    pdocs, counts = positions_to_doc_counts(sess.execute(f'"{ph}"'),
                                            pidx.doc_starts)
    assert np.array_equal(got, rank_docs(pdocs, counts, 4))


# ----------------------------------------------------------------------
# device path: batched dedup == host
# ----------------------------------------------------------------------
@pytest.mark.parametrize("store", ["repair_skip", "vbyte"])
def test_batched_doclist_matches_host(col, store):
    idx = NonPositionalIndex.build(col.docs, store=store)
    pidx2 = PositionalIndex.build(col.docs, store=store)
    sess = Session(idx, positional=pidx2,
                   server=BatchedServer.from_index(idx),
                   positional_server=BatchedServer.from_index(pidx2))
    host = Session(idx, positional=pidx2)
    words = [w for w in idx.vocab.id_to_token[:20]]
    toks = tokenize(col.docs[0])
    queries = [f"docs: {words[1]} {words[4]}",
               f"docs: {words[2]} {words[3]} {words[5]}",
               f'docs: "{toks[0]}"',
               f'docs: "{toks[1]} {toks[2]}"',
               "docs: zzz-unknown-term"]
    plans = [sess.plan(q) for q in queries]
    assert [p.route for p in plans[:4]] == ["device"] * 4, plans
    got = sess.execute(queries)
    for q, g in zip(queries, got):
        h = host.execute(q)
        assert np.array_equal(np.asarray(g), np.asarray(h)), (store, q)


def test_positional_only_docs_and_stays_on_host(col):
    """Regression: a positional-only session with a device server must NOT
    route non-phrase `docs:` conjunctions to the device — the AND step
    would intersect disjoint *position* lists and return empty; the host
    intersects per-term document runs."""
    pidx2 = PositionalIndex.build(col.docs, store="repair_skip")
    sess = Session(None, positional=pidx2,
                   positional_server=BatchedServer.from_index(pidx2))
    toks = tokenize(col.docs[0])
    q = f"docs: {toks[0]} {toks[2]}"
    pl = sess.plan(q)
    assert pl.index == "positional" and pl.route == "host", pl
    got = sess.execute([q])[0]
    want = Session(None, positional=pidx2).execute(q)
    assert len(want) > 0 and np.array_equal(np.asarray(got), want)
    # phrase doc listing still takes the device route and agrees
    pq = f'docs: "{toks[0]} {toks[1]}"'
    assert sess.plan(pq).route == "device"
    dev = sess.execute([pq])[0]
    host = Session(None, positional=pidx2).execute(pq)
    assert np.array_equal(np.asarray(dev), np.asarray(host))
