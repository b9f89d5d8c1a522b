"""The sweep's two binary searches, bounded by the slices they search.

A lower-bound search over n entries has converged after ``n.bit_length()``
halvings, so the anchor search runs the bit length of the longest probed
list's C entries and the pool search that of the longest entry's
postings, not a fixed 32 and ``bit_length(max_phrase) + 1``.  The host
reckons both once per window (``search_steps``) and the step runs them.
These tests pin that reckoning, that the bounded searches answer exactly
as NumPy set membership on both layouts, that lanes whose answer is
discarded do not raise the bound, that the bound is traced (no new jit
trace when it changes) and that each ``engine.window`` span records the
halvings its program ran.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anchors import (
    FULL_DEPTH,
    MAX_SEARCH_STEPS,
    build_anchored,
    build_compressed_anchored,
    lower_bound,
    member_batch,
    member_batch_compressed,
)
from repro.core.index import NonPositionalIndex
from repro.serving import tracing
from repro.serving.engine import (
    _PAD_VAL,
    _probe_terms,
    candidates_for,
    fused_candidates_for,
    search_steps,
)
from repro.serving.session import Session

from lane_budget import lane_budget_windows

#: slices of 2^k - 1, 2^k and 2^k + 1 entries, an empty and a one-entry one
SIZES = (0, 1, 2, 3, 4, 5, 7, 8, 9, 15, 16, 17, 63, 64, 65, 255, 256, 257)


@pytest.mark.parametrize("n", SIZES + (1023, 1024, 1025))
def test_search_steps_are_the_bit_lengths_of_the_probed_slices(n):
    """A query that probes a list of n C entries, the longest of n
    postings: n.bit_length() halvings for each search; a padding slot on
    that list, or a query with no candidate, counts for nothing."""
    c_off = np.asarray([0, n, n + 1])  # list 0: n entries; list 1: one
    longest = np.asarray([n, 1])
    qt = np.asarray([[1, 0]], np.int32)
    two, one, live = np.asarray([2]), np.asarray([1]), np.asarray([True])
    got = search_steps(c_off, longest, qt, two, live)
    assert got.dtype == np.int32
    assert got.tolist() == [n.bit_length(), n.bit_length()]
    assert search_steps(c_off, None, qt, two, live).tolist() == [
        n.bit_length(), 0]
    assert search_steps(c_off, longest, qt, one, live).tolist() == [0, 0]
    assert search_steps(c_off, longest, qt, two, ~live).tolist() == [0, 0]


@pytest.mark.parametrize("n", SIZES)
def test_lower_bound_converges_in_bit_length_halvings(n):
    """Over a slice of n entries inside a longer array, ``n.bit_length()``
    halvings find every target's lower bound, below the first key and
    above the last too; one halving fewer misses some target."""
    rng = np.random.default_rng(n)
    keys = np.sort(rng.choice(10 * n + 40, size=n + 8, replace=False))
    lo, hi = 5, 5 + n
    targets = np.arange(keys[0] - 2, keys[-1] + 3)
    want = lo + np.searchsorted(keys[lo:hi], targets, side="left")

    def search(steps):
        lanes = np.full(len(targets), lo), np.full(len(targets), hi)
        return np.asarray(lower_bound(jnp.asarray(keys, jnp.int32),
                                      *(jnp.asarray(a, jnp.int32) for a in lanes),
                                      jnp.asarray(targets, jnp.int32),
                                      jnp.int32(steps)))

    assert np.array_equal(search(n.bit_length()), want)
    assert np.array_equal(search(MAX_SEARCH_STEPS + 5), want)  # capped at 32
    if n:
        assert not np.array_equal(search(n.bit_length() - 1), want)


def _lists(grammar: str) -> list[np.ndarray]:
    """``postings``: one C entry per posting, so each list's C-entry slice
    is one of SIZES.  ``rules``: repetitive lists that Re-Pair folds into
    long entries, with an empty and a one-posting list among them."""
    rng = np.random.default_rng(2718)
    if grammar == "postings":
        return [np.sort(rng.choice(600, size=n, replace=False)) for n in SIZES]
    base = np.sort(rng.choice(600, size=240, replace=False))
    lists = [base[rng.random(len(base)) >= drop] for drop in
             (0.0, 0.02, 0.05, 0.1, 0.3, 0.6)]
    return lists + [np.zeros(0, np.int64), np.asarray([7]), base[::3] + 1]


def _index(layout: str, grammar: str):
    kw = {"max_rules": 0} if grammar == "postings" else {}
    lists = [np.asarray(l, np.int64) for l in _lists(grammar)]
    build = build_compressed_anchored if layout == "fused" else build_anchored
    return lists, build(lists, **kw)


def _reckoned(idx, ids) -> np.ndarray:
    """(anchor, pool) halvings by brute force: the bit length of the most C
    entries of a list among ``ids`` and of the most postings of one of
    their entries (no pool search on the dense layout)."""
    c_off = np.asarray(idx.c_offsets)
    widest = max((int(c_off[i + 1] - c_off[i]) for i in ids), default=0)
    longest = 0
    if hasattr(idx, "c_len"):
        c_len = np.asarray(idx.c_len)
        longest = max((int(c_len[j]) for i in ids
                       for j in range(c_off[i], c_off[i + 1])), default=0)
    return np.asarray([widest.bit_length(), longest.bit_length()], np.int32)


@pytest.mark.parametrize("grammar", ["postings", "rules"])
@pytest.mark.parametrize("layout", ["fused", "dense"])
def test_bounded_membership_equals_numpy(layout, grammar):
    """Every value from below each list's first posting to above its last,
    probed in every list: the answers of the searches bounded by the live
    slices, and by the brute-force count, equal ``np.isin``."""
    lists, idx = _index(layout, grammar)
    if layout == "fused" and grammar == "rules":
        c_len = np.asarray(idx.c_len)
        assert idx.max_phrase > 8 and (c_len == idx.max_phrase).any()
    top = max(int(l.max()) for l in lists if len(l))
    vals = np.arange(0, top + 3)
    ids = np.repeat(np.arange(len(lists)), len(vals)).astype(np.int32)
    vals = np.tile(vals, len(lists)).astype(np.int32)
    want = np.concatenate([np.isin(np.arange(0, top + 3), l) for l in lists])
    member = member_batch_compressed if layout == "fused" else member_batch
    args = idx, jnp.asarray(ids), jnp.asarray(vals)
    assert np.array_equal(np.asarray(member(*args)), want)
    steps = _reckoned(idx, range(len(lists)))
    assert np.array_equal(np.asarray(member(*args, steps=jnp.asarray(steps))),
                          want)


@pytest.mark.parametrize("layout", ["fused", "dense"])
def test_lanes_past_the_bound_leave_the_others_exact(layout):
    """Lanes whose answer is discarded point at the longest list, and the
    bound is what the short list needs: they stop short inside their own
    slice, and the lanes on the short list answer exactly."""
    lists, idx = _index(layout, "postings")
    longest = int(np.argmax([len(l) for l in lists]))
    short = SIZES.index(5)
    ids = np.asarray([short] * 8 + [longest] * 8, np.int32)
    vals = np.tile(np.arange(0, 600, 75), 2).astype(np.int32)
    member = member_batch_compressed if layout == "fused" else member_batch
    steps = _reckoned(idx, [short])
    assert steps[0] < len(lists[longest]).bit_length()
    got = np.asarray(member(idx, jnp.asarray(ids), jnp.asarray(vals),
                            steps=jnp.asarray(steps)))
    assert np.array_equal(got[:8], np.isin(vals[:8], lists[short]))


@pytest.mark.parametrize("layout", ["fused", "dense"])
def test_padding_slots_on_the_longest_list_change_nothing(layout):
    """A batch whose padding term slots (past ``ql``) name the longest list:
    the host bound leaves them out, and the answers under it, and under
    FULL_DEPTH, are those of padding with list 0."""
    lists, idx = _index(layout, "postings")
    longest = int(np.argmax([len(l) for l in lists]))
    drive, other = SIZES.index(17), SIZES.index(9)
    ql = np.asarray([2, 2, 1], np.int32)
    pad0 = np.asarray([[drive, other, 0], [other, drive, 0], [drive, 0, 0]],
                      np.int32)
    padded = pad0.copy()
    padded[:, 2] = longest
    padded[2, 1] = longest
    c_off = np.asarray(idx.c_offsets)
    ok = np.ones(3, bool)
    assert search_steps(c_off, None, padded, ql, ok)[0] == 5
    if layout == "fused":
        first = jnp.asarray(padded[:, 0])
        cand = fused_candidates_for(idx, idx.c_offsets[first],
                                    idx.c_offsets[first + 1])
    else:
        cand = candidates_for(idx, jnp.asarray(padded[:, 0]), 0)
    answers = []
    for qt in (pad0, padded):
        for steps in (FULL_DEPTH, jnp.asarray(_reckoned(idx, [drive, other]))):
            match = _probe_terms(idx, jnp.asarray(qt), jnp.asarray(ql), *cand,
                                 3, phrase=False, steps=steps)
            answers.append(np.asarray(match))
    for a in answers[1:]:
        assert np.array_equal(a, answers[0])
    vals = np.asarray(cand[0]) - 1
    for i, (a, b) in enumerate(pad0[:, :2]):
        want = np.isin(vals[i], lists[a])
        if ql[i] == 2:
            want &= np.isin(vals[i], lists[b])
        assert np.array_equal(vals[i][answers[0][i]],
                              vals[i][np.asarray(cand[1])[i] & want])


@pytest.mark.parametrize("layout", ["fused", "dense"])
def test_phrase_shifts_near_the_sentinel(layout):
    """Shifted targets near ``_PAD_VAL`` under the bound of the live slices:
    real pairs below the top match, a shift past the universe does not."""
    top = _PAD_VAL - 2
    l0 = np.asarray([10, top - 4, top - 1, top], np.int64)
    l1 = np.asarray([11, top - 3, top], np.int64)
    build = build_compressed_anchored if layout == "fused" else build_anchored
    idx = build([l0, l1, np.arange(40, 90, dtype=np.int64)])
    qt, ql = jnp.asarray([[0, 1]], jnp.int32), jnp.asarray([2], jnp.int32)
    if layout == "fused":
        cand = fused_candidates_for(idx, idx.c_offsets[qt[:, 0]],
                                    idx.c_offsets[qt[:, 0] + 1])
    else:
        cand = candidates_for(idx, qt[:, 0], 0)
    for steps in (FULL_DEPTH, jnp.asarray(_reckoned(idx, [1]))):
        match = _probe_terms(idx, qt, ql, *cand, 2, phrase=True, steps=steps)
        got = np.asarray(cand[0])[np.asarray(match)] - 1
        assert np.array_equal(np.unique(got), [10, top - 4, top - 1])


# ----------------------------------------------------------------------
# the served path: a traced bound and the span's counts
# ----------------------------------------------------------------------
def _docs() -> list[str]:
    """``qrare`` in one document, ``qmid`` in every seventh, ``qoften`` in
    all but every fifth, ``qall`` in all of them, plus filler that
    repeats."""
    docs = []
    for d in range(700):
        words = ["qall", f"f{d % 13}", f"g{d % 29}"]
        words += ["qoften"] * (d % 5 != 0) + ["qmid"] * (d % 7 == 0)
        words += ["qrare"] * (d == 350)
        docs.append(" ".join(words))
    return docs


@pytest.fixture(scope="module", params=["fused", "dense"])
def served(request):
    idx = NonPositionalIndex.build(_docs(), store="repair_skip")
    session = Session.build(idx, layout=request.param)
    return idx, session, Session.build(idx, device=False)


def _window_spans(run):
    t0 = time.perf_counter()
    out = run()
    return out, [r for r in tracing.spans(t0, time.perf_counter()).records
                 if r.name == "engine.window"]


def test_the_bound_is_traced(served):
    """After warm-up, a batch that probes a one-entry list and one that
    probes the longest list run other step counts under the same compiled
    step, and both answer as the host engine."""
    idx, session, host = served
    server = session.server
    c_entries = [server.c_entries(i) for i in range(len(server._lengths_np))]
    widest = idx.vocab.id_to_token[int(np.argmax(c_entries))]
    assert server.c_entries(idx.word_id("qrare")) == 1 < max(c_entries)
    small = [["qrare", "qrare"]] * 4  # probes a one-entry list
    large = [["qrare", widest]] * 4  # probes the widest list
    server.conjunctive(small)  # warm-up
    traces = server.trace_events
    steps = []
    for batch in (large, small, large):
        got, windows = _window_spans(lambda: server.conjunctive(batch))
        steps.append({w.attrs["anchor_steps"] for w in windows})
        for q, a in zip(batch, got):
            assert np.array_equal(a, host.execute(" ".join(q))), q
    assert server.trace_events == traces
    assert steps[0] == steps[2] and len(steps[0] | steps[1]) > 1


def test_window_spans_carry_the_steps_their_program_ran(served):
    """``anchor_steps`` and ``pool_steps`` of every window equal the bit
    length of the most C entries, and of the most postings of one entry,
    of the lists the window's live queries probe, counted from the
    store; padding slots and a query with an unknown word count for
    nothing."""
    idx, session, host = served
    server = session.server
    store = idx.store
    queries = [["qoften", "qall"], ["qmid", "qoften", "qall"], ["qrare", "f3"],
               ["nosuchword", "qall"], ["qall", "g7"]]
    answers, windows = _window_spans(
        lambda: server.conjunctive(queries, width=4))
    for q, a in zip(queries, answers):
        assert np.array_equal(a, host.execute(" ".join(q))), q
    c_off = store.c_offsets

    def entries(word):
        i = idx.word_id(word)
        return [store.symbol_len(int(s)) for s in store.c[c_off[i]:c_off[i + 1]]]

    # rarest word drives; the others are probed, in each window the query
    # still has postings in
    known = [sorted(q, key=lambda w: len(idx.query_word(w)))
             for q in queries if "nosuchword" not in q]
    fused = server.layout == "fused"
    for w in windows:
        live = []
        for q in known:
            i = idx.word_id(q[0])
            if fused:
                n = len(lane_budget_windows(server, i))
            else:
                n = -(-len(entries(q[0])) // 64)
            if w.attrs["window"] < n:
                live += q[1:]
        widest = max((len(entries(t)) for t in live), default=0)
        longest = max((max(entries(t)) for t in live), default=0)
        assert w.attrs["anchor_steps"] == widest.bit_length()
        assert w.attrs["pool_steps"] == (longest.bit_length() if fused else 0)
    assert windows and max(w.attrs["anchor_steps"] for w in windows) > 0


def test_sharded_window_spans_carry_the_most_steps_a_shard_ran():
    """Every shard runs the most halvings any shard's own slices need; the
    span gives that count, reckoned from each shard's C offsets, and the
    answers are the host engine's."""
    from repro.serving.partitioned import PartitionedServer

    idx = NonPositionalIndex.build(_docs(), store="repair_skip")
    server = PartitionedServer.from_index(idx, n_shards=3)
    host = Session.build(idx, device=False)
    queries = [["qmid", "qoften"], ["qrare", "qall"], ["nosuchword", "qall"]]
    answers, windows = _window_spans(lambda: server.conjunctive(queries))
    for q, a in zip(queries, answers):
        assert np.array_equal(a, host.execute(" ".join(q))), q
    c = np.asarray(server.pidx.arrays["c_offsets"])
    pairs = [(idx.word_id(a), idx.word_id(b)) for a, b in queries[:2]]
    for w in windows:
        start = 64 * w.attrs["window"]
        want = max((int(cs[p + 1] - cs[p]) for cs in c for d, p in pairs
                    if cs[d + 1] - cs[d] > start), default=0)
        assert w.attrs["anchor_steps"] == want.bit_length() > 0
        assert w.attrs["pool_steps"] == 0


@pytest.mark.parametrize("layout", ["fused", "dense"])
def test_the_kernel_probe_records_no_halvings(layout):
    """``probe="kernel"`` finds the covering entry by the Pallas kernels'
    tiled compares, not by halvings: its windows record none, and it
    answers as the host engine."""
    idx = NonPositionalIndex.build(_docs(), store="repair_skip")
    server = Session.build(idx, layout=layout, probe="kernel").server
    host = Session.build(idx, device=False)
    queries = [["qmid", "qoften"], ["qrare", "qall"]]
    answers, windows = _window_spans(lambda: server.conjunctive(queries))
    for q, a in zip(queries, answers):
        assert np.array_equal(a, host.execute(" ".join(q))), q
    assert windows
    assert all(w.attrs["anchor_steps"] == w.attrs["pool_steps"] == 0
               for w in windows)
