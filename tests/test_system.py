"""End-to-end system behaviour: collection -> compressed indexes -> queries
-> serving session; anchored TPU path == CPU skipping path."""

import jax.numpy as jnp
import numpy as np

from repro.core.anchors import build_anchored, member_batch
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.serving.session import Session


def test_end_to_end_search(small_collection):
    idx = NonPositionalIndex.build(small_collection.docs, store="repair_skip")
    session = Session(idx)
    words = [w for w in idx.vocab.id_to_token[:20]]
    hits = session.execute(f"{words[1]} {words[4]}")
    # every reported doc really contains both words
    for d in hits.tolist():
        low = small_collection.docs[d].lower()
        assert words[1] in low and words[4] in low
    ranked = session.execute(f"top3: {words[1]} {words[4]}")
    assert len(ranked) <= 3
    assert set(ranked.tolist()) <= set(hits.tolist())


def test_anchored_path_matches_cpu_path(small_collection):
    idx = NonPositionalIndex.build(small_collection.docs, store="repair_skip")
    store = idx.store
    lists = [store.get_list(i) for i in range(min(25, store.n_lists))]
    aidx = build_anchored(lists)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, len(lists), 200).astype(np.int32)
    vals = rng.integers(0, idx.n_docs + 5, 200).astype(np.int32)
    got = np.asarray(member_batch(aidx, jnp.asarray(ids), jnp.asarray(vals)))
    ref = np.asarray([int(v) in set(lists[i].tolist()) for i, v in zip(ids, vals)])
    assert np.array_equal(got, ref)


def test_positional_and_nonpositional_consistency(small_collection):
    np_idx = NonPositionalIndex.build(small_collection.docs, store="vbyte",
                                      case_fold=False, drop_stopwords=False)
    pos_idx = PositionalIndex.build(small_collection.docs, store="vbyte")
    w = [t for t in pos_idx.vocab.id_to_token if t.isalpha()][5]
    pos_hits = pos_idx.query_word(w)
    docs = np.unique(pos_idx.positions_to_docs(pos_hits)[0])
    np_hits = np_idx.query_word(w)
    assert np.array_equal(docs, np_hits)


def test_compression_improves_with_repetitiveness():
    frac = {}
    for edit_rate in (0.002, 0.2):
        col = generate_collection(n_articles=4, versions_per_article=10,
                                  words_per_doc=100, edit_rate=edit_rate, seed=2)
        idx = NonPositionalIndex.build(col.docs, store="repair_skip")
        frac[edit_rate] = idx.space_fraction
    assert frac[0.002] < frac[0.2]


def test_collection_stats_table():
    col = generate_collection(n_articles=3, versions_per_article=5, words_per_doc=50)
    s = col.stats()
    assert s["versions"] == 15 and s["articles"] == 3
    assert s["versions_per_article"] == 5.0
