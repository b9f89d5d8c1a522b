"""Capability registry + SearchBackend protocol (the unified backend API).

Parity suite: every registered backend — inverted store or self-index
adapter — must return the same word / AND / phrase answers as a raw NumPy
reference over a small repetitive collection, through the same index /
engine API.  Plus the registry crash paths: unknown names and stray build
kwargs are clear ValueErrors.
"""

import numpy as np
import pytest

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.core.registry import (
    ALL_CAPABILITIES,
    FAMILY_INVERTED,
    FAMILY_SELFINDEX,
    CAP_EXTRACT,
    CAP_SHIFTED_INTERSECT,
    backend_names,
    build_backend,
    capabilities_of,
    get_backend_spec,
)
from repro.data import generate_collection
from repro.data.text import is_word_token, tokenize
from repro.serving.session import Session

ALL_BACKENDS = backend_names()
INVERTED = backend_names(family=FAMILY_INVERTED)
SELFINDEX = backend_names(family=FAMILY_SELFINDEX)


@pytest.fixture(scope="module")
def tiny_collection():
    return generate_collection(n_articles=2, versions_per_article=4,
                               words_per_doc=50, seed=13)


def brute_docs(docs, words):
    out = []
    for d, doc in enumerate(docs):
        toks = {t.lower() for t in tokenize(doc) if is_word_token(t)}
        if all(w in toks for w in words):
            out.append(d)
    return np.asarray(out, dtype=np.int64)


def brute_phrase(stream, ids):
    m = len(ids)
    return np.asarray([p for p in range(len(stream) - m + 1)
                       if all(stream[p + j] == ids[j] for j in range(m))], np.int64)


# ----------------------------------------------------------------------
# registry metadata + crash paths
# ----------------------------------------------------------------------
def test_registry_families_complete():
    assert len(INVERTED) == 20  # the paper's store zoo + the mined rlz
    assert "rlz" in INVERTED
    assert set(SELFINDEX) >= {"rlcsa", "wcsa", "lz77_idx", "lzend_idx"}
    assert set(ALL_BACKENDS) == set(INVERTED) | set(SELFINDEX)


def test_unknown_backend_is_value_error():
    with pytest.raises(ValueError, match="unknown backend 'nope'.*repair_skip"):
        build_backend("nope", [np.arange(3)])
    with pytest.raises(ValueError, match="registered backends"):
        NonPositionalIndex.build(["a b c"], store="not_a_store")
    with pytest.raises(ValueError, match="registered backends"):
        from repro.core.registry import restore_backend

        restore_backend("definitely_missing", {})


def test_bad_build_kwargs_are_value_error():
    lists = [np.arange(4, dtype=np.int64), np.asarray([1, 3], dtype=np.int64)]
    with pytest.raises(ValueError, match="unexpected build kwargs.*accepted: k"):
        build_backend("vbyte_cm", lists, sample_every=8)
    with pytest.raises(ValueError, match="unexpected build kwargs"):
        NonPositionalIndex.build(["a b c d"], store="vbyte", bogus=1)
    # valid kwargs still forward uniformly through the registry
    st = build_backend("vbyte_cm", lists, k=4)
    assert np.array_equal(st.get_list(0), lists[0])


def test_selfindex_needs_stream():
    with pytest.raises(ValueError, match="self-index.*token"):
        build_backend("rlcsa", [np.arange(3)])


def test_unknown_backend_error_lists_every_registered_name():
    """The PR-2 contract: the unknown-name ValueError names the live
    registry, not a subset — a user can copy any listed name and proceed."""
    with pytest.raises(ValueError) as ei:
        build_backend("nope", [np.arange(3)])
    msg = str(ei.value)
    for name in ALL_BACKENDS:
        assert name in msg, f"{name!r} missing from: {msg}"


def test_bad_kwargs_error_lists_accepted_names():
    """The stray-kwarg ValueError names both the offender and the full
    accepted set (or says there is none)."""
    lists = [np.arange(4, dtype=np.int64)]
    with pytest.raises(ValueError) as ei:
        build_backend("repair_skip_st", lists, window=9, B=4)
    msg = str(ei.value)
    assert "window" in msg and "accepted: B" in msg
    # backends with no build kwargs say so instead of listing nothing
    with pytest.raises(ValueError, match=r"accepted: \(none\)"):
        build_backend("vbyte", lists, k=3)


def test_index_build_propagates_registry_errors():
    """Both index builders surface the same registry ValueErrors eagerly
    (before any tokenization work)."""
    for builder in (NonPositionalIndex.build, PositionalIndex.build):
        with pytest.raises(ValueError, match="registered backends.*vbyte"):
            builder(["a b c"], store="definitely_missing")
        with pytest.raises(ValueError, match="unexpected build kwargs.*accepted: sample_rate"):
            builder(["a b c"], store="rlcsa", sample_rate_typo=8)


def test_declared_capabilities_are_valid_and_match_instances(tiny_collection):
    for name in ALL_BACKENDS:
        spec = get_backend_spec(name)
        assert spec.capabilities <= ALL_CAPABILITIES
        idx = PositionalIndex.build(tiny_collection.docs[:3], store=name)
        assert capabilities_of(idx.store) == spec.capabilities, name


# ----------------------------------------------------------------------
# parity: every backend vs the NumPy reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("store", ALL_BACKENDS)
def test_nonpositional_parity(tiny_collection, store):
    docs = tiny_collection.docs
    idx = NonPositionalIndex.build(docs, store=store)
    words = [w for w in idx.vocab.id_to_token[:16]]
    for q in ([words[2]], [words[1], words[5]], [words[0], words[3], words[7]]):
        ref = brute_docs(docs, q)
        got = idx.query_and(q) if len(q) > 1 else idx.query_word(q[0])
        assert np.array_equal(np.sort(np.unique(got)), ref), (store, q)
    assert idx.size_in_bits > 0


@pytest.mark.parametrize("store", ALL_BACKENDS)
def test_positional_phrase_parity(tiny_collection, store):
    docs = tiny_collection.docs
    idx = PositionalIndex.build(docs, store=store, keep_text=True)
    stream = idx.token_stream
    toks = tokenize(docs[0])
    for ph in ([toks[0]], toks[1:3], toks[4:8]):
        ids = [idx.token_id(t) for t in ph]
        assert all(i is not None for i in ids)
        ref = brute_phrase(stream, ids)
        got = np.sort(np.asarray(idx.query_phrase(list(ph))))
        assert np.array_equal(got, ref), (store, ph)


@pytest.mark.parametrize("store", SELFINDEX)
def test_selfindex_extract_roundtrip(tiny_collection, store):
    """`extract` capability: the token stream is recoverable from the index."""
    idx = PositionalIndex.build(tiny_collection.docs[:3], store=store, keep_text=True)
    assert CAP_EXTRACT in capabilities_of(idx.store)
    lo, hi = 5, 25
    assert np.array_equal(idx.store.extract(lo, hi), idx.token_stream[lo : hi + 1])


# ----------------------------------------------------------------------
# cross-family agreement through the unified engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("store", ["rlcsa", "lzend_idx"])
def test_engine_selfindex_matches_inverted(tiny_collection, store):
    """Acceptance: word and phrase queries against self-index backends go
    through the same plan/execute API and equal the inverted answers."""
    docs = tiny_collection.docs
    ref = Session(NonPositionalIndex.build(docs, store="repair_skip"),
                  positional=PositionalIndex.build(docs, store="repair_skip"))
    sess = Session(NonPositionalIndex.build(docs, store=store),
                   positional=PositionalIndex.build(docs, store=store))
    assert CAP_SHIFTED_INTERSECT in capabilities_of(sess.index.store)
    words = [w for w in ref.index.vocab.id_to_token[:12]]
    ph = tokenize(docs[0])[2:5]
    queries = [words[1], f"{words[1]} {words[4]}", '"' + " ".join(ph) + '"',
               f"top3: {words[1]} {words[4]}", "xyzzy-not-a-word"]
    for q in queries:
        plan = sess.plan(q)
        assert plan.route == "host"
        got, want = sess.execute(q), ref.execute(q)
        assert np.array_equal(np.sort(np.asarray(got)), np.sort(np.asarray(want))), (store, q)
    assert sess.plan(f"{words[1]} {words[4]}").strategy == "self-locate"


def test_partitioned_from_index_any_backend(tiny_collection):
    """The sharded layout builds from any backend through the protocol."""
    from repro.serving.partitioned import PartitionedAnchoredIndex

    docs = tiny_collection.docs
    idx = NonPositionalIndex.build(docs, store="vbyte_st")
    pidx = PartitionedAnchoredIndex.from_index(idx, n_shards=2)
    assert pidx.n_shards == 2
    assert int(pidx.doc_bounds[-1]) == idx.n_docs
    # positional sharding cuts at document boundaries
    p = PositionalIndex.build(docs, store="vbyte")
    ppidx = PartitionedAnchoredIndex.from_index(p, n_shards=2)
    assert int(ppidx.doc_bounds[1]) in p.doc_starts
    assert int(ppidx.doc_bounds[-1]) == p.n_tokens
