"""Batched device phrase/top-k serving vs the host positional index.

The acceptance bar for the batched serving subsystem: identical
(doc, offset) phrase results to host ``PositionalIndex.query_phrase`` on a
repetitive versioned collection, across several list stores, including
driving lists longer than one 64-candidate window.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.text import tokenize
from repro.serving.engine import MAX_CAND_ROWS, BatchedServer, make_serve_step
from repro.serving.plan import parse_query
from repro.serving.session import Session

STORES = ["repair_skip", "vbyte", "elias_fano"]


@pytest.fixture(scope="module")
def col():
    return generate_collection(n_articles=6, versions_per_article=15,
                               words_per_doc=120, seed=11)


@pytest.fixture(scope="module")
def phrase_queries(col):
    rng = np.random.default_rng(3)
    out = []
    for _ in range(12):
        doc = col.docs[int(rng.integers(len(col.docs)))]
        toks = tokenize(doc)
        i = int(rng.integers(0, max(1, len(toks) - 3)))
        out.append(toks[i : i + 2 + int(rng.integers(2))])
    out.append(["zzz", "not-in-vocab"])  # unknown terms -> empty
    return out


@pytest.mark.parametrize("store", STORES)
def test_phrase_matches_host(col, phrase_queries, store):
    pidx = PositionalIndex.build(col.docs, store=store)
    server = BatchedServer.from_index(pidx)
    got = server.phrase(phrase_queries)
    for q, dev_pos in zip(phrase_queries, got):
        host_pos = np.sort(np.asarray(pidx.query_phrase(q)))
        assert np.array_equal(dev_pos, host_pos), (store, q)
        # identical (doc, offset) pairs, not just raw positions
        hd, ho = pidx.positions_to_docs(host_pos)
        dd, do = pidx.positions_to_docs(dev_pos)
        assert np.array_equal(hd, dd) and np.array_equal(ho, do), (store, q)


def test_phrase_step_covers_long_lists():
    """Driving lists longer than one candidate window are served exactly
    (the old MAX_CAND_ROWS=64 truncation would drop the tail)."""
    rng = np.random.default_rng(5)
    n = 40_000
    # incompressible positional lists: ~1 C-entry per posting after Re-Pair,
    # so ~6000 postings >> 64 candidate rows
    a = np.sort(rng.choice(n, 6000, replace=False)).astype(np.int64)
    b = np.sort(np.unique(np.concatenate(
        [a[::2] + 1, rng.choice(n, 3000)]))).astype(np.int64)
    c = np.sort(np.unique(np.concatenate(
        [a[::3] + 2, rng.choice(n, 2000)]))).astype(np.int64)
    from repro.core.anchors import build_anchored

    aidx = build_anchored([a, b, c])
    c_off = np.asarray(aidx.c_offsets)
    assert c_off[1] - c_off[0] > MAX_CAND_ROWS, "driving list must span >1 window"

    ref = a[np.isin(a + 1, b) & np.isin(a + 2, c)]
    arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
              "expand": aidx.expand, "expand_valid": aidx.expand_valid,
              "lengths": aidx.lengths}
    import jax

    step = jax.jit(make_serve_step(max_terms=3, mode="phrase"))
    qt = jnp.asarray([[0, 1, 2]], jnp.int32)
    ql = jnp.asarray([3], jnp.int32)
    hits = []
    n_win = -(-int(c_off[1] - c_off[0]) // MAX_CAND_ROWS)
    assert n_win > 1
    for w in range(n_win):
        vals, mask = step(arrays, qt, ql, w * MAX_CAND_ROWS)
        hits.append(np.asarray(vals)[0][np.asarray(mask)[0]])
    got = np.unique(np.concatenate(hits))
    assert np.array_equal(got, ref)
    # ... and the truncated single window would NOT have been enough
    assert len(hits[0]) < len(ref) or len(ref) == 0


def test_topk_matches_host_ranked_and(col):
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    server = BatchedServer.from_index(idx)
    host = Session(idx)
    rng = np.random.default_rng(9)
    words = [w for w in idx.vocab.id_to_token[:150]]
    queries = [[words[int(rng.integers(len(words)))] for _ in range(2)]
               for _ in range(10)]
    dev = server.topk(queries, k=5)
    for q, d in zip(queries, dev):
        want = host.execute(f"top5: {q[0]} {q[1]}")
        assert np.array_equal(np.asarray(d), np.asarray(want)), q


def test_planner_routes_mixed_batch(col):
    idx = NonPositionalIndex.build(col.docs, store="repair_skip")
    pidx = PositionalIndex.build(col.docs, store="repair_skip")
    sess = Session(idx, positional=pidx,
                   server=BatchedServer.from_index(idx),
                   positional_server=BatchedServer.from_index(pidx))
    toks = tokenize(col.docs[0])[:3]
    w = [t for t in idx.vocab.id_to_token[:10]][:2]
    queries = [w[0], f"{w[0]} {w[1]}", '"' + " ".join(toks) + '"',
               f"top3: {w[0]} {w[1]}"]
    kinds = [parse_query(q).kind for q in queries]
    assert kinds == ["word", "and", "phrase", "topk"]
    routes = [sess.plan(q).route for q in queries]
    assert routes[0] == "host" and set(routes[1:]) == {"device"}
    res = sess.execute(queries)
    host = Session(idx, positional=pidx).execute(queries)
    for r, h in zip(res, host):
        assert np.array_equal(np.asarray(r), np.asarray(h))


def test_parse_query_forms():
    assert parse_query("a").kind == "word"
    assert parse_query("a b").kind == "and"
    assert parse_query('"a b"').kind == "phrase"
    q = parse_query("top7: a b")
    assert q.kind == "topk" and q.k == 7 and q.terms == ("a", "b")
    assert parse_query(["a"]).kind == "word"
    assert parse_query(["a", "b"]).kind == "and"
