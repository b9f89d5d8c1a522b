"""Query-plan IR: parse validation, plan compilation (golden EXPLAIN
snapshots), Session execution semantics, and plan/trace caching.

The golden snapshots pin the physical operator tree — operator choice,
cost-model output, routing — for one backend per family (run-length / LZ /
grammar / self-index) over a handcrafted deterministic collection: any
unintended change to the capability→operator mapping or the cost model
shows up as a readable diff.  The differential test asserts the acceptance
criterion: ``Session.execute`` on a shuffled mixed-kind batch returns
byte-identical answers to host-only per-query execution across ≥6
backends, and a repeated mixed batch performs **zero re-plans and zero new
jit traces** on its second submission.
"""

import numpy as np
import pytest

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.serving.plan import (
    DocReduce,
    Intersect,
    PhraseMatch,
    ScoredReduce,
    TermScan,
    TopK,
    logical_plan,
    parse_query,
    unparse,
    width_bucket,
)
from repro.serving.session import Session

# deterministic 4-doc collection: every golden number below derives from it
DOCS_FIXTURE = [
    "grammar index list query grammar index",
    "grammar index list serve serve query",
    "grammar list plan query index grammar",
    "plan serve index grammar list query",
]


def _host_session(store: str) -> Session:
    return Session(NonPositionalIndex.build(DOCS_FIXTURE, store=store),
                   positional=PositionalIndex.build(DOCS_FIXTURE, store=store))


# ----------------------------------------------------------------------
# parse_query: grammar validation
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bad", [
    "", "   ", "\t \n",          # empty / whitespace-only
    '""', '"   "',               # empty phrase
    'docs: ""',                  # empty phrase doc listing
    "top0: a b",                 # zero-k ranked AND
    "docs-top0: a b",            # zero-k ranked retrieval
    "rank0: a b",                # zero-k BM25 ranking
    "docs:", "top5:", "docs-top3:   ", "rank3:",  # prefix with no terms at all
    [], (),                      # empty legacy list form
])
def test_parse_query_rejects_malformed(bad):
    with pytest.raises(ValueError, match="accepted query grammar"):
        parse_query(bad)


def test_parse_query_analyzer_strips_everything():
    # every term is a stopword: the analyzed rank query has no terms left
    with pytest.raises(ValueError, match="accepted query grammar"):
        parse_query("rank3: the of and", analyzer="default")
    with pytest.raises(ValueError, match="stripped every term"):
        parse_query("rank3: the of and", analyzer="default")
    # the raw chain keeps stopwords, so the same query parses
    assert parse_query("rank3: the of and", analyzer="raw").terms == (
        "the", "of", "and")


def test_parse_query_accepts_the_grammar():
    assert parse_query("a").kind == "word"
    assert parse_query("a b").kind == "and"
    assert parse_query('"a b"').kind == "phrase"
    assert parse_query("top7: a b").k == 7
    assert parse_query("docs-top2: a b").k == 2
    assert parse_query('docs: "a b"').phrase
    rq = parse_query("rank6: a b")
    assert rq.kind == "rank" and rq.k == 6 and not rq.analyzed
    assert parse_query("rank6: Plan b", analyzer="default").terms == ("plan", "b")
    assert parse_query("rank6: Plan b", analyzer="default").analyzed
    # round trip: unparse(parse) is stable
    for q in ("a", "a b", '"a b"', "top7: a b", "docs: a b", 'docs: "a b"',
              "docs-top2: a b", 'docs-top2: "a b"', "rank6: a b"):
        assert unparse(parse_query(q)) == q


def test_logical_plan_tree_shapes():
    assert logical_plan("a") == TermScan("a")
    assert logical_plan("a b") == Intersect((TermScan("a"), TermScan("b")))
    assert logical_plan('"a b"') == PhraseMatch(("a", "b"))
    t = logical_plan("top3: a b")
    assert isinstance(t, TopK) and t.k == 3 and t.score == "idf"
    d = logical_plan('docs: "a b"')
    assert isinstance(d, DocReduce) and isinstance(d.child, PhraseMatch)
    dt = logical_plan("docs-top2: a b")
    assert (isinstance(dt, TopK) and dt.score == "tf"
            and isinstance(dt.child, DocReduce) and dt.child.counts)
    r = logical_plan("rank2: a b")
    assert (isinstance(r, TopK) and r.score == "bm25"
            and isinstance(r.child, ScoredReduce)
            and r.child.terms == ("a", "b"))


def test_width_bucket_powers_of_two():
    assert [width_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == [2, 2, 4, 4, 8, 8, 16]


def test_index_stats_surface():
    idx = NonPositionalIndex.build(DOCS_FIXTURE, store="vbyte")
    st = idx.stats()
    assert st is idx.stats()  # computed once, cached
    # 6 distinct words over 4 docs; 'grammar'/'index'/'list'/'query' in 4/4/4/4
    assert st.n_lists == idx.store.n_lists
    assert st.universe_size == 4
    assert st.n_postings == sum(idx.store.list_length(i)
                                for i in range(idx.store.n_lists))
    assert st.max_list_length == 4
    assert idx.term_length("grammar") == 4
    assert idx.term_length("zzz-unknown") == 0
    pst = PositionalIndex.build(DOCS_FIXTURE, store="vbyte").stats()
    # every token posted once, except the per-doc separators (never queried)
    assert pst.universe_size == pst.n_postings + len(DOCS_FIXTURE)


# ----------------------------------------------------------------------
# golden EXPLAIN snapshots: one backend per family, all query kinds
# ----------------------------------------------------------------------
GOLDEN_HOST = {
    # run-length family
    ("rice_runs", "grammar query"): """\
query: grammar query
kind=and index=nonpositional backend=rice_runs route=host strategy=svs-merge
svs-merge  rows~4 cost~8
├─ list-decode  rows~4 cost~4  (term 'grammar')
└─ list-decode  rows~4 cost~4  (term 'query')""",
    ("rice_runs", '"grammar index"'): """\
query: "grammar index"
kind=phrase index=positional backend=rice_runs route=host strategy=svs-merge
svs-merge  rows~1 cost~11  (offset-shifted intersection)
├─ list-decode  rows~6 cost~6  (term 'grammar')
└─ list-decode  rows~5 cost~5  (term 'index')""",
    # LZ family
    ("vbyte_lzend", "index"): """\
query: index
kind=word index=nonpositional backend=vbyte_lzend route=host strategy=svs-merge
list-decode  rows~4 cost~4  (term 'index')""",
    ("vbyte_lzend", "docs: grammar query"): """\
query: docs: grammar query
kind=docs index=nonpositional backend=vbyte_lzend route=host strategy=doclist+svs-merge
distinct-docs  rows~4 cost~12  (postings are doc ids already)
└─ svs-merge  rows~4 cost~8
   ├─ list-decode  rows~4 cost~4  (term 'grammar')
   └─ list-decode  rows~4 cost~4  (term 'query')""",
    # grammar family: compressed-domain skipping
    ("repair_skip", "top2: grammar query"): """\
query: top2: grammar query
kind=topk index=nonpositional backend=repair_skip route=host strategy=compressed-skip
topk-idf  rows~2 cost~20  (k=2 score=idf)
└─ compressed-skip  rows~4 cost~12
   ├─ list-decode  rows~4 cost~4  (term 'grammar')
   └─ list-decode  rows~4 cost~4  (term 'query')""",
    ("repair_skip", 'docs: "grammar index"'): """\
query: docs: "grammar index"
kind=docs index=positional backend=repair_skip route=host strategy=reduce-doclist
reduce-doclist  rows~1 cost~16  (run intersect + reduce)
└─ compressed-skip  rows~1 cost~15  (offset-shifted intersection)
   ├─ list-decode  rows~6 cost~6  (term 'grammar')
   └─ list-decode  rows~5 cost~5  (term 'index')""",
    # self-index family: native locate
    ("rlcsa", "grammar query"): """\
query: grammar query
kind=and index=nonpositional backend=rlcsa route=host strategy=self-locate
self-locate  rows~4 cost~6  (native per-word locates, intersected)
├─ locate  rows~4 cost~4  (term 'grammar')
└─ locate  rows~4 cost~4  (term 'query')""",
    ("rlcsa", 'docs: "grammar index"'): """\
query: docs: "grammar index"
kind=docs index=positional backend=rlcsa route=host strategy=self-doclist
self-doclist  rows~1 cost~8  (locate whole pattern, reduce to docs)
└─ self-locate  rows~1 cost~7  (one native locate of the whole pattern)
   ├─ locate  rows~6 cost~6  (term 'grammar')
   └─ locate  rows~5 cost~5  (term 'index')""",
    # ranked retrieval: upper-bound pruning surfaced in the plan — 'plan'
    # (rare, high idf) is scored fully, 'grammar' (in every doc) prunable
    ("repair_skip", "rank2: plan grammar"): """\
query: rank2: plan grammar
kind=rank index=nonpositional backend=repair_skip route=host strategy=wand-maxscore
wand-topk  rows~2 cost~7  (k=2 score=bm25; 1 fully-scored + 1 prunable list(s), est skip 67%)
└─ scored-doc-runs  rows~4 cost~8  (BM25 over per-term (doc, tf) runs + doc lengths)
   ├─ list-decode  rows~2 cost~2  (term 'plan')
   └─ list-decode  rows~4 cost~4  (term 'grammar')""",
}

GOLDEN_DEVICE = {
    '"grammar index"': """\
query: "grammar index"
kind=phrase index=positional backend=repair_skip route=device strategy=anchored-phrase layout=fused
device-windowed-sweep  rows~1 cost~128  (1 window(s) x 64 candidates, shifted probes on device, width=2, layout=fused)
├─ list-decode  rows~6 cost~6  (term 'grammar')
└─ list-decode  rows~5 cost~5  (term 'index')""",
    "top2: grammar query": """\
query: top2: grammar query
kind=topk index=nonpositional backend=repair_skip route=device strategy=anchored-topk layout=fused
device-topk  rows~2 cost~136  (k=2 score=idf)
└─ device-windowed-sweep  rows~4 cost~128  (1 window(s) x 64 candidates, probes on device, width=2, layout=fused)
   ├─ list-decode  rows~4 cost~4  (term 'grammar')
   └─ list-decode  rows~4 cost~4  (term 'query')""",
    "rank2: plan grammar": """\
query: rank2: plan grammar
kind=rank index=nonpositional backend=repair_skip route=device strategy=device-ranked
device-ranked  rows~2 cost~16  (k=2 score=bm25; dense scatter-add + lax.top_k, width=2)
└─ scored-doc-runs  rows~4 cost~8  (BM25 over per-term (doc, tf) runs + doc lengths)
   ├─ list-decode  rows~2 cost~2  (term 'plan')
   └─ list-decode  rows~4 cost~4  (term 'grammar')""",
}


@pytest.mark.parametrize("store,query", sorted(GOLDEN_HOST, key=str))
def test_explain_golden_host(store, query):
    got = _host_session(store).explain(query)
    assert got == GOLDEN_HOST[(store, query)], f"\n--- got ---\n{got}"


def test_explain_golden_device():
    sess = Session.build(NonPositionalIndex.build(DOCS_FIXTURE, store="repair_skip"),
                         positional=PositionalIndex.build(DOCS_FIXTURE,
                                                          store="repair_skip"))
    for query, want in GOLDEN_DEVICE.items():
        got = sess.explain(query)
        assert got == want, f"\n--- got ---\n{got}"


def test_explain_json_shape():
    d = _host_session("repair_skip").explain("docs: grammar query", fmt="json")
    assert d["kind"] == "docs" and d["route"] == "host"
    assert d["strategy"] == "doclist+compressed-skip"
    assert d["plan"]["op"] == "distinct-docs"
    assert [c["op"] for c in d["plan"]["children"]] == ["compressed-skip"]
    with pytest.raises(ValueError, match="explain format"):
        _host_session("vbyte").explain("a", fmt="yaml")


def test_explain_requires_the_needed_index():
    sess = Session(NonPositionalIndex.build(DOCS_FIXTURE, store="vbyte"))
    with pytest.raises(ValueError, match="positional index"):
        sess.explain('"grammar index"')


# ----------------------------------------------------------------------
# differential: Session.execute == host-only per-query execution, ≥6 backends
# ----------------------------------------------------------------------
DIFF_BACKENDS = ("vbyte", "rice_runs", "vbyte_st", "repair_skip",
                 "vbyte_lzend", "rlcsa")


@pytest.fixture(scope="module")
def diff_collection():
    from repro.data import generate_collection

    return generate_collection(n_articles=2, versions_per_article=4,
                               words_per_doc=45, edit_rate=0.2, seed=11)


def _mixed_batch(col, idx, rng):
    from repro.data.text import tokenize

    vocab = idx.vocab.id_to_token
    w = [vocab[int(rng.integers(len(vocab)))] for _ in range(6)]
    toks = tokenize(col.docs[0])[3:5]
    batch = [
        w[0], f"{w[1]} {w[2]}", f"{w[0]} {w[3]} {w[4]}",
        '"' + " ".join(toks) + '"', f"top4: {w[1]} {w[2]}",
        f"docs: {w[0]}", f"docs: {w[1]} {w[2]}",
        'docs: "' + " ".join(toks) + '"', f"docs-top3: {w[1]} {w[2]}",
        "zzz-unknown-term", f"{w[0]} zzz-unknown-term",
        f"rank4: {w[1]} {w[2]}", f"rank3: {w[0]} zzz-unknown-term",
    ]
    rng.shuffle(batch)
    return batch


@pytest.mark.parametrize("store", DIFF_BACKENDS)
def test_session_matches_queryengine_per_query(diff_collection, store):
    col = diff_collection
    idx = NonPositionalIndex.build(col.docs, store=store)
    pidx = PositionalIndex.build(col.docs, store=store)
    sess = Session.build(idx, positional=pidx)  # device where applicable
    ref = Session(idx, positional=pidx)  # host-only, query by query
    rng = np.random.default_rng(17)
    batch = _mixed_batch(col, idx, rng)
    got = sess.execute(batch)
    for q, g in zip(batch, got):
        want = np.asarray(ref.execute(q))
        g = np.asarray(g)
        assert g.dtype == want.dtype and np.array_equal(g, want), (
            f"store={store!r} query={q!r} session={g.tolist()} "
            f"host={want.tolist()}")


# ----------------------------------------------------------------------
# plan cache + jit trace stability (the acceptance criterion)
# ----------------------------------------------------------------------
def test_repeated_mixed_batch_zero_replans_zero_retraces(diff_collection):
    col = diff_collection
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    pidx = PositionalIndex.build(col.docs, store="repair_skip")
    sess = Session.build(idx, positional=pidx)
    rng = np.random.default_rng(23)
    batch = _mixed_batch(col, idx, rng)
    first = sess.execute(batch)
    m1 = sess.metrics()
    assert m1["plans_compiled"] > 0 and m1["jit_traces"] > 0
    # second submission, shuffled: same shapes -> same plans, same traces
    order = rng.permutation(len(batch))
    second = sess.execute([batch[i] for i in order])
    m2 = sess.metrics()
    assert m2["plans_compiled"] == m1["plans_compiled"], "re-planned a cached shape"
    assert m2["jit_traces"] == m1["jit_traces"], "re-traced a cached step"
    assert m2["plan_cache_hits"] == m1["plan_cache_hits"] + len(batch)
    for i, j in enumerate(order):
        assert np.array_equal(np.asarray(second[i]), np.asarray(first[j]))
    # a genuinely new shape does compile (counters are live, not frozen)
    sess.execute("docs-top2: " + " ".join(batch[0].split()[:1]))
    assert sess.metrics()["plans_compiled"] == m2["plans_compiled"] + 1


def test_warmed_ranked_traffic_full_hit_rate_zero_retraces(diff_collection):
    """Acceptance: steady ranked traffic re-plans and re-traces nothing —
    after the warming pass the plan-cache hit rate on repeated ``rank<k>:``
    batches is 1.00 and the jit trace count is flat."""
    col = diff_collection
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    sess = Session.build(idx)
    vocab = idx.vocab.id_to_token
    rng = np.random.default_rng(29)
    w = [vocab[int(rng.integers(len(vocab)))] for _ in range(8)]
    batch = [f"rank4: {w[0]} {w[1]}", f"rank4: {w[2]} {w[3]}",
             f"rank4: {w[4]} {w[5]}", f"rank4: {w[6]} {w[7]}"]
    assert all(sess.plan(q).route == "device" for q in batch)
    first = sess.execute(batch)
    warm = sess.metrics()
    fresh = Session.build(idx)
    fresh.execute(batch)  # warm a fresh session, then measure only repeats
    fresh.plans_compiled = fresh.plan_cache_hits = 0
    for _ in range(3):
        again = fresh.execute(batch)
        for a, b in zip(again, first):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    m = fresh.metrics()
    assert m["plan_cache_hit_rate"] == 1.0, m
    assert m["jit_traces"] == warm["jit_traces"], "ranked traffic re-traced"


def test_width_bucketing_shares_traces_across_term_counts(diff_collection):
    col = diff_collection
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    sess = Session.build(idx)
    vocab = idx.vocab.id_to_token
    sess.execute([f"{vocab[1]} {vocab[2]} {vocab[3]}"])  # 3 terms -> width 4
    t = sess.jit_traces
    sess.execute([f"{vocab[4]} {vocab[5]} {vocab[6]} {vocab[7]}"])  # 4 -> width 4
    assert sess.jit_traces == t, "3- and 4-term AND queries must share a trace"


# ----------------------------------------------------------------------
# sharded serving through the Session (PartitionedServer)
# ----------------------------------------------------------------------
def test_partitioned_server_under_session(diff_collection):
    from repro.serving.partitioned import PartitionedAnchoredIndex, PartitionedServer

    col = diff_collection
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    shards = PartitionedAnchoredIndex.from_index(idx, n_shards=2)
    sess = Session(idx, server=PartitionedServer(shards, idx))
    host = Session(idx)
    vocab = idx.vocab.id_to_token
    q_and = f"{vocab[1]} {vocab[2]}"
    assert sess.plan(q_and).route == "device"
    # doc listing is not a shard-local step: plan keeps it on the host
    assert sess.plan(f"docs: {vocab[1]} {vocab[2]}").route == "host"
    batch = [q_and, f"{vocab[3]} {vocab[1]} {vocab[2]}", vocab[4]]
    got = sess.execute(batch)
    want = host.execute(batch)
    for q, g, w in zip(batch, got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), q
    t = sess.jit_traces
    assert t > 0
    sess.execute(batch)
    assert sess.jit_traces == t  # shard steps cached too


# ----------------------------------------------------------------------
# Extract: snippet windows through the plan surface
# ----------------------------------------------------------------------
def test_extract_windows_match_stream():
    pidx = PositionalIndex.build(DOCS_FIXTURE, store="vbyte", keep_text=True)
    sess = Session(positional=pidx)
    wins = sess.extract('"grammar index"', context=1)
    pos = np.asarray(pidx.query_phrase(["grammar", "index"]))
    assert len(wins) == len(pos) > 0
    for p, w in zip(pos.tolist(), wins):
        lo, hi = max(0, p - 1), min(pidx.n_tokens, p + 3)
        assert np.array_equal(w, pidx.token_stream[lo:hi])
    # self-index backends extract from the index itself (no stored text)
    si = Session(positional=PositionalIndex.build(DOCS_FIXTURE, store="rlcsa"))
    wins_si = si.extract('"grammar index"', context=1)
    assert len(wins_si) == len(wins)
    for a, b in zip(wins, wins_si):
        assert np.array_equal(a, b)
    with pytest.raises(ValueError, match="extract"):
        Session(positional=PositionalIndex.build(DOCS_FIXTURE, store="vbyte")) \
            .extract('"grammar index"')
    ex = sess.explain('"grammar index"', extract=1)
    assert "stored-text-slice" in ex
    assert "extract-direct" in si.explain('"grammar index"', extract=1)
