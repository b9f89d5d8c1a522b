"""Fused device layout: compressed postings in HBM, decode inside the sweep.

Pins the tentpole invariants of the fused layout: (a) the compressed form
is strictly smaller than the dense expand tables — >= 4x on repetitive
collections — and (b) every serve kind (word / AND / phrase / topk / docs)
returns byte-identical results under both layouts and both probe
implementations.  Also pins the build-time side-effect fix (``from_store``
must not mutate the caller's store) and the shifted-probe guard at the top
of the universe.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anchors import (
    AnchoredIndex,
    CompressedAnchoredIndex,
    build_anchored,
    build_compressed_anchored,
    member_batch,
    member_batch_compressed,
)
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.core.repair import RePairStore
from repro.data import generate_collection
from repro.serving.engine import BatchedServer, _probe_terms, candidates_for
from repro.serving.session import Session

rng = np.random.default_rng(20260808)


def _repetitive_docs(edit_rate: float = 0.1):
    return generate_collection(n_articles=2, versions_per_article=6,
                               words_per_doc=50, edit_rate=edit_rate,
                               seed=99).docs


def _lists(n_lists: int = 10, drop: float = 0.05) -> list[np.ndarray]:
    base = np.sort(rng.choice(4000, size=300, replace=False))
    out = []
    for _ in range(n_lists):
        keep = rng.random(len(base)) >= drop
        out.append(base[keep].astype(np.int64))
    return out


# ----------------------------------------------------------------------
# device-memory accounting
# ----------------------------------------------------------------------
def test_compressed_device_bytes_le_dense():
    lists = _lists()
    store = RePairStore.build(lists, variant="skip")
    dense = AnchoredIndex.from_store(store)
    comp = CompressedAnchoredIndex.from_store(store)
    assert comp.device_bytes() <= dense.device_bytes()


@pytest.mark.parametrize("positional", [False, True])
def test_fused_server_bytes_4x_smaller_on_repetitive(positional):
    """The acceptance bound: on the repetitive fixture collections the
    fused layout holds >= 4x less HBM than the dense expand tables."""
    docs = _repetitive_docs()
    builder = PositionalIndex.build if positional else NonPositionalIndex.build
    idx = builder(docs, store="repair_skip")
    dense = BatchedServer.from_index(idx, layout="dense")
    fused = BatchedServer.from_index(idx, layout="fused")
    assert fused.device_bytes() * 4 <= dense.device_bytes(), (
        fused.device_bytes(), dense.device_bytes())


def test_auto_layout_fuses_device_resident_stores():
    docs = _repetitive_docs()
    fused = BatchedServer.from_index(
        NonPositionalIndex.build(docs, store="repair_skip"))
    dense = BatchedServer.from_index(
        NonPositionalIndex.build(docs, store="vbyte"))
    assert fused.layout == "fused" and "pool" in fused.arrays
    assert dense.layout == "dense" and "expand" in dense.arrays
    # explicit fused works for any backend (re-compressed from its lists)
    forced = BatchedServer.from_index(
        NonPositionalIndex.build(docs, store="vbyte"), layout="fused")
    assert forced.layout == "fused"
    with pytest.raises(ValueError, match="layout"):
        BatchedServer.from_index(
            NonPositionalIndex.build(docs, store="vbyte"), layout="bogus")


# ----------------------------------------------------------------------
# byte-identical serving across layouts
# ----------------------------------------------------------------------
@pytest.mark.parametrize("probe", ["vmap", "kernel"])
def test_fused_vs_dense_identical_all_kinds(probe):
    docs = _repetitive_docs(edit_rate=0.2)
    np_idx = NonPositionalIndex.build(docs, store="repair_skip")
    pos_idx = PositionalIndex.build(docs, store="repair_skip")
    vocab = sorted(np_idx.vocab.token_to_id)[:8]
    queries = [[vocab[0]], [vocab[1], vocab[2]], vocab[:3], ["zzz-missing"]]
    for layout_pair in [("dense", "fused")]:
        a = BatchedServer.from_index(np_idx, probe=probe, layout=layout_pair[0])
        b = BatchedServer.from_index(np_idx, probe=probe, layout=layout_pair[1])
        for kind in ("conjunctive", "doclist", "topk"):
            for x, y in zip(getattr(a, kind)(queries), getattr(b, kind)(queries)):
                assert np.array_equal(x, y), (kind, probe, x, y)
        pa = BatchedServer.from_index(pos_idx, probe=probe, layout=layout_pair[0])
        pb = BatchedServer.from_index(pos_idx, probe=probe, layout=layout_pair[1])
        toks = docs[0].split()[:2]
        pqs = [toks, [toks[0]], ["zzz-missing", toks[0]]]
        for x, y in zip(pa.phrase(pqs), pb.phrase(pqs)):
            assert np.array_equal(x, y), (probe, x, y)
        for x, y in zip(pa.doclist(pqs, phrase=True), pb.doclist(pqs, phrase=True)):
            assert np.array_equal(x, y), (probe, x, y)


def test_session_execute_identical_across_layouts():
    """End-to-end through the plan-cached Session entry point."""
    docs = _repetitive_docs(edit_rate=0.2)
    np_idx = NonPositionalIndex.build(docs, store="repair_skip")
    pos_idx = PositionalIndex.build(docs, store="repair_skip")
    w = sorted(np_idx.vocab.token_to_id)[:3]
    phrase = " ".join(docs[0].split()[:2])
    queries = [f"{w[0]} {w[1]}", f'"{phrase}"', f"top3: {w[0]} {w[1]}",
               f"docs: {w[0]} {w[2]}"]
    fused = Session.build(np_idx, positional=pos_idx, layout="fused")
    dense = Session.build(np_idx, positional=pos_idx, layout="dense")
    for q in queries:
        assert np.array_equal(fused.execute(q), dense.execute(q)), q
    # the layout is part of the plan shape: EXPLAIN names it
    assert "layout=fused" in fused.explain(queries[0])
    assert "layout=dense" in dense.explain(queries[0])


def test_member_batch_compressed_parity():
    lists = _lists()
    lists[3] = np.zeros(0, dtype=np.int64)  # empty list never matches
    store = RePairStore.build(lists, variant="skip")
    dense = AnchoredIndex.from_store(store)
    comp = CompressedAnchoredIndex.from_store(store)
    ids = rng.integers(0, len(lists), 600).astype(np.int32)
    vals = rng.integers(0, 4200, 600).astype(np.int32)
    ref = member_batch(dense, jnp.asarray(ids), jnp.asarray(vals))
    got = member_batch_compressed(comp, jnp.asarray(ids), jnp.asarray(vals))
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# ----------------------------------------------------------------------
# build-time side effect (from_store must not mutate the store)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("build", [AnchoredIndex.from_store,
                                   CompressedAnchoredIndex.from_store])
def test_from_store_keeps_store_state(build):
    store = RePairStore.build(_lists(4), variant="skip")
    assert store.memoize is False and store._memo == {}
    build(store)
    assert store.memoize is False, "build leaked memoize=True into the store"
    assert store._memo == {}, "build leaked its expansion cache into the store"
    # a caller that opted into memoization keeps its setting and cache
    store.memoize = True
    store.expand_symbol(int(store.c[0]))
    cached = dict(store._memo)
    build(store)
    assert store.memoize is True
    assert set(cached).issubset(store._memo)


# ----------------------------------------------------------------------
# shifted probes at the top of the universe (PAD_VAL sentinel guard)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "fused"])
@pytest.mark.parametrize("probe", ["vmap", "kernel"])
def test_phrase_probe_at_universe_top(layout, probe):
    """A driving posting at universe_size - 1 shifts past every legal
    posting: the shifted target must neither wrap int32 nor collide with
    the probe kernel's PAD_VAL sentinel — real pairs below it still match."""
    top = 2**31 - 3  # largest posting whose cumulative value stays < PAD_VAL
    l0 = np.asarray([10, top - 3, top], dtype=np.int64)  # driving list
    l1 = np.asarray([11, top - 2, top - 1], dtype=np.int64)  # +1 probes
    lists = [l0, l1]
    if layout == "fused":
        idx = build_compressed_anchored(lists)
    else:
        idx = build_anchored(lists)
    from repro.serving.engine import (_kernel_member, _kernel_member_fused,
                                      fused_candidates_for)
    member = None
    if probe == "kernel":
        member = (_kernel_member_fused(interpret=True) if layout == "fused"
                  else _kernel_member(interpret=True))
    qt = jnp.asarray([[0, 1]], jnp.int32)
    ql = jnp.asarray([2], jnp.int32)
    if layout == "fused":  # the whole driving list: entries [lo, hi)
        cand_vals, cand_valid = fused_candidates_for(
            idx, idx.c_offsets[qt[:, 0]], idx.c_offsets[qt[:, 0] + 1])
    else:
        cand_vals, cand_valid = candidates_for(idx, qt[:, 0], 0)
    match = _probe_terms(idx, qt, ql, cand_vals, cand_valid, 2, phrase=True,
                         member=member)
    got = np.unique(np.asarray(cand_vals)[np.asarray(match)]) - 1
    # 10->11 and (top-3)->(top-2) are real phrase pairs; top->top+1 is out
    # of the universe and must NOT match (sentinel collision would say yes)
    assert np.array_equal(got, np.asarray([10, top - 3])), got


# ----------------------------------------------------------------------
# the fused generator's ragged expansion
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_generator_packs_each_range_from_lane_zero(seed):
    """Any C-entry range whose postings fit the window comes out as the
    entries' decoded postings, in order, packed from lane 0 — whatever
    the entries' lengths — and nothing else is valid."""
    from repro.serving.engine import fused_candidates_for, window_lanes

    r = np.random.default_rng(seed)
    idx = build_compressed_anchored(_lists(n_lists=12, drop=0.3))
    anchors, ptr, ln = (np.asarray(a) for a in (idx.anchors, idx.c_ptr,
                                                idx.c_len))
    pool = np.asarray(idx.pool)
    lanes = window_lanes(idx.max_phrase)
    n_c = len(anchors)
    lo = r.integers(0, n_c, size=6)
    hi = lo.copy()
    for i in range(len(lo)):  # extend each range while its postings fit
        while (hi[i] < n_c and r.random() < 0.9
               and ln[lo[i]:hi[i] + 1].sum() <= lanes):
            hi[i] += 1
    lo[0] = hi[0]  # an empty range too
    vals, valid = fused_candidates_for(idx, jnp.asarray(lo, jnp.int32),
                                       jnp.asarray(hi, jnp.int32))
    vals, valid = np.asarray(vals), np.asarray(valid)
    assert vals.shape == valid.shape == (len(lo), lanes)
    for i in range(len(lo)):
        want = [anchors[j] + pool[ptr[j]:ptr[j] + ln[j]]
                for j in range(lo[i], hi[i])]
        want = np.concatenate(want) if want else np.zeros(0, np.int64)
        assert valid[i].sum() == len(want)
        assert valid[i][:len(want)].all()
        assert np.array_equal(vals[i][:len(want)], want)
