"""Batched device serving: the device-side steps and their host driver.

The serving stack is plan-first:

* ``serving.plan`` — `parse_query` → logical plan → cost-aware compiler →
  physical plan (`route_query` / `compile_query` / EXPLAIN rendering).
* ``serving.session.Session`` — the **only** entry point: plan-cached,
  jit-bucket-grouped `execute`, plus `explain` and `metrics`.
* this module — the device-side batched steps (:func:`make_serve_step`)
  and the windowed-exact device driver (:class:`BatchedServer`).  It
  imports nothing from ``serving.session``.

Device-step geometry: padded (batch, width) term-id matrices; each step
generates candidates from the query's first list via the bounded expansion
table and probes the remaining terms through the anchored binary search
(``member_batch``).  Phrase queries probe *shifted* candidates
(offset-shifted intersection, paper §3): term ``t`` of a phrase must hold
``position + t``.  Candidate generation is **windowed**: the host driver
sweeps a cursor over the driving list's C-entries so arbitrarily long
lists are served exactly.  On the dense layout a window is MAX_CAND_ROWS
expand rows of every list; on the fused layout it is budgeted in postings,
each query's C entries ``[row_lo, row_hi)`` expanded into
``window_lanes(max_phrase)`` lanes.  Ranked top-k computes idf-proxy
weights on device and reduces with ``lax.top_k``; document listing maps matches to
doc ids and dedups on device with a segment-max scan.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.anchors import (
    FULL_DEPTH,
    MAX_SEARCH_STEPS,
    AnchoredIndex,
    CompressedAnchoredIndex,
    build_anchored,
    build_compressed_anchored,
    member_batch,
    member_batch_compressed,
)
from ..core.doclist import BM25_B, BM25_K1, bm25_idf
from ..core.index import NonPositionalIndex, PositionalIndex
from ..core.registry import CAP_DEVICE_RESIDENT, capabilities_of
from . import tracing
from .plan import AND, MAX_CAND_ROWS, PHRASE, RANK, SERVER_KINDS


def _lookup(index, term: str):
    return index.lookup(term)


def encode_queries(host_index, lengths: np.ndarray, queries: list[list[str]],
                   sort_by_length: bool = False, width: int | None = None
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad term lists to (B, width) id matrices — the shared encode step of
    every batched device driver (``BatchedServer``, ``PartitionedServer``).

    ``width`` defaults to the batch's longest query; the Session passes its
    power-of-two bucket so equal shapes share jit traces.  Queries with any
    unknown term are marked invalid (their result is empty; the padded row
    still flows through the step so shapes stay static).  With
    ``sort_by_length`` (AND / top-k only — order matters for phrases) the
    rarest term under ``lengths`` drives candidate generation, which
    minimizes the window sweep."""
    longest = max(len(q) for q in queries)
    if width is None:
        width = max(2, longest)
    elif width < longest:
        raise ValueError(f"width {width} < longest query ({longest} terms)")
    qt = np.zeros((len(queries), width), np.int32)
    ql = np.ones(len(queries), np.int32)
    ok = np.ones(len(queries), bool)
    for i, q in enumerate(queries):
        ids = [_lookup(host_index, t) for t in q]
        if any(v is None for v in ids):
            ok[i] = False
            continue
        if sort_by_length:
            ids = sorted(ids, key=lambda w: lengths[w])
        qt[i, : len(ids)] = ids
        ql[i] = len(ids)
    return qt, ql, ok


# ----------------------------------------------------------------------
# device-side batched steps (uihrdc arch)
# ----------------------------------------------------------------------
def candidates_for(idx: AnchoredIndex, list_ids: jax.Array,
                   row_start: jax.Array | int = 0) -> tuple[jax.Array, jax.Array]:
    """MAX_CAND_ROWS * expand_len absolute values of each list, starting at
    C-entry ``row_start`` of the list (the windowed candidate generator —
    sweeping ``row_start`` covers lists of any length exactly).

    Returns (values (B, C), valid (B, C)) in cumulative-gap space.
    """
    lo = idx.c_offsets[list_ids] + row_start
    hi = idx.c_offsets[list_ids + 1]
    rows = lo[:, None] + jnp.arange(MAX_CAND_ROWS)[None, :]
    valid_rows = rows < hi[:, None]
    rows = jnp.minimum(rows, idx.expand.shape[0] - 1)
    vals = idx.expand[rows]  # (B, ROWS, L)
    valid = idx.expand_valid[rows] & valid_rows[:, :, None]
    b = list_ids.shape[0]
    return vals.reshape(b, -1), valid.reshape(b, -1)


_PAD_VAL = 2**31 - 1  # anchor_intersect's sentinel (shifted targets stay below)

#: a fused window's lanes per query are a whole number of these
WINDOW_LANE_TILE = 1024


def window_lanes(max_phrase: int) -> int:
    """Lanes per query of a fused-layout sweep window: ``max_phrase``
    rounded up to a multiple of WINDOW_LANE_TILE.  No C entry expands to
    more than ``max_phrase`` postings, so every entry fits one window and
    the sweep always advances."""
    tiles = -(-max(int(max_phrase), 1) // WINDOW_LANE_TILE)
    return tiles * WINDOW_LANE_TILE


def fused_candidates_for(idx: CompressedAnchoredIndex, row_lo: jax.Array,
                         row_hi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused-layout candidate generator: the postings of each query's C
    entries ``[row_lo, row_hi)`` (absolute entry numbers, int32[B]),
    expanded from the shared prefix-summed pool into
    ``window_lanes(idx.max_phrase)`` lanes, packed from lane 0.

    The host planner keeps each range's postings within the lanes, so the
    expansion is ragged with no per-entry padding: lane ``k`` belongs to
    the last entry that starts at or before it (a scatter of each entry's
    index at its start, carried right by ``cummax``).  Returns (values
    (B, lanes), valid (B, lanes)) in cumulative-gap space, ascending along
    each query's valid lanes — the dense generator's postings for the
    same entries, without its padding.
    """
    lanes = window_lanes(idx.max_phrase)
    b = row_lo.shape[0]
    k = jnp.arange(lanes, dtype=jnp.int32)
    # every entry holds at least one posting, so at most `lanes` entries fit
    rows = row_lo[:, None] + k[None, :]
    live = rows < row_hi[:, None]
    rows = jnp.minimum(rows, idx.anchors.shape[0] - 1)
    lens = jnp.where(live, idx.c_len[rows], 0)
    starts = jnp.cumsum(lens, axis=1) - lens
    total = starts[:, -1] + lens[:, -1]
    # cummax, not associative_scan, for the reason doclist_dedup gives;
    # masked entries scatter 0, which the max ignores
    owner = jnp.zeros((b, lanes), jnp.int32).at[
        jnp.arange(b)[:, None], starts].max(jnp.where(live, k, 0), mode="drop")
    owner = jax.lax.cummax(owner, axis=1)
    base = jnp.take_along_axis(idx.anchors[rows], owner, axis=1)
    # pool slot of lane k: its entry's pointer plus (k - the entry's start)
    slot = jnp.take_along_axis(idx.c_ptr[rows] - starts, owner, axis=1) + k
    valid = k[None, :] < total[:, None]
    vals = base + idx.pool[jnp.where(valid, slot, 0)]
    return vals, valid


def _probe_terms(idx, query_terms, query_lens, cand_vals, cand_valid,
                 max_terms: int, phrase: bool, member=None, steps=FULL_DEPTH):
    """AND / phrase probe loop shared by all steps.  For phrase queries term
    ``t`` probes candidate + t (offset-shifted intersection, §3).  ``member``
    swaps the probe implementation: by default the binary searches of the
    index's layout, which run ``steps``, the (anchor, pool) halvings of
    :func:`search_steps`; or the Pallas kernels via ``probe="kernel"``."""
    if member is None:
        member = partial(member_batch_compressed
                         if isinstance(idx, CompressedAnchoredIndex)
                         else member_batch, steps=steps)
    b, nc = cand_vals.shape
    match = cand_valid
    for t in range(1, max_terms):
        term = query_terms[:, t]
        active = (t < query_lens)[:, None]
        flat_ids = jnp.repeat(term, nc)
        if phrase:
            # shifted target is cand_vals + t in cumulative-gap space; clamp
            # so postings near the top of the universe can neither wrap int32
            # nor collide with the probe kernel's PAD_VAL sentinel
            safe = cand_vals <= _PAD_VAL - 1 - t
            shifted = jnp.where(safe, cand_vals, 0) - 1 + t
        else:
            safe = None
            shifted = cand_vals - 1
        hit = member(idx, flat_ids, shifted.reshape(-1)).reshape(b, nc)
        if safe is not None:
            hit = hit & safe
        match = match & jnp.where(active, hit, True)
    return match


def _kernel_member(interpret: bool):
    from ..kernels.anchor_intersect.ops import member_batch_tpu

    def member(idx: AnchoredIndex, list_ids, values):
        return member_batch_tpu(idx.anchors, idx.c_offsets, idx.expand,
                                idx.expand_valid, list_ids, values,
                                interpret=interpret)

    return member


def _kernel_member_fused(interpret: bool):
    """Fused-layout kernel probe: ``anchor_intersect``'s sliced lower bound
    finds the covering C entry, then ``fused_decode.probe_rows`` expands it
    from the pool and compares — decoded postings never touch HBM."""
    from ..kernels.anchor_intersect.ops import anchor_probe_sliced
    from ..kernels.fused_decode.ops import probe_rows

    def member(idx: CompressedAnchoredIndex, list_ids, values):
        targets = values.astype(jnp.int32) + 1
        lo = idx.c_offsets[list_ids]
        hi = idx.c_offsets[list_ids + 1]
        l = anchor_probe_sliced(targets, lo, hi, idx.anchors, interpret=interpret)
        j = jnp.maximum(l - 1, lo)
        L = max(int(idx.max_phrase), 1)
        gaps = jax.vmap(
            lambda p: jax.lax.dynamic_slice_in_dim(idx.pool, p, L)
        )(idx.c_ptr[j])
        hit = probe_rows(gaps, idx.anchors[j], idx.c_len[j], targets,
                         interpret=interpret)
        return hit & (lo < hi)

    return member


def _idf_weights(idx: AnchoredIndex, query_terms, query_lens, max_terms: int,
                 n_docs: float) -> jax.Array:
    """Per-query idf-proxy weight: sum over active terms of
    log1p(n_docs / list_len) — the device form of ranked_and's host loop.

    Note this is one scalar per *query* (the non-positional index has no
    per-document frequencies), so among a query's matches the ranking
    degenerates to doc-id order — exactly like host ``ranked_and``, whose
    weight vector is constant too.  The score is still attached to every
    hit so a downstream per-document ranker can slot in here."""
    w = jnp.zeros(query_terms.shape[0], jnp.float32)
    for t in range(max_terms):
        ell = jnp.maximum(idx.lengths[query_terms[:, t]], 1).astype(jnp.float32)
        w = w + jnp.where(t < query_lens, jnp.log1p(n_docs / ell), 0.0)
    return w


def _as_anchored(index: dict) -> AnchoredIndex:
    return AnchoredIndex(
        anchors=index["anchors"],
        c_offsets=index["c_offsets"],
        expand=index["expand"],
        expand_valid=index["expand_valid"],
        lengths=index["lengths"],
        expand_len=index["expand"].shape[-1],
    )


def _as_compressed(index: dict, max_phrase: int) -> CompressedAnchoredIndex:
    # max_phrase is a static decode bound, not an array — the step closure
    # carries it (it would otherwise be traced away inside jit)
    return CompressedAnchoredIndex(
        anchors=index["anchors"],
        c_offsets=index["c_offsets"],
        c_ptr=index["c_ptr"],
        c_len=index["c_len"],
        pool=index["pool"],
        lengths=index["lengths"],
        max_phrase=max_phrase,
    )


def make_serve_step(max_terms: int = 8, mode: str = AND, topk: int = 0,
                    n_docs: float = 0.0, probe: str = "vmap",
                    doclist: bool = False, layout: str = "dense",
                    max_phrase: int = 0):
    """Build a batched device step.

    ``mode`` is "and" (conjunctive doc queries) or "phrase" (offset-shifted
    positional probes).  The step is called as ``serve(index, query_terms,
    query_lens, *cursor, steps)``, where the cursor names the sweep
    window: on the dense layout ``row_start``, the window's first C entry
    within every driving list (default 0); on the fused layout ``row_lo,
    row_hi``, each query's absolute C-entry range, int32[B] (see
    :func:`fused_candidates_for`).  ``steps``, int32[2], is the halvings
    of the anchor and pool searches, as :func:`search_steps` reckons them
    for the window (by default FULL_DEPTH, which suits any window); it is
    traced, so a batch that needs other counts reuses the compiled step.
    With ``topk == 0`` the step returns ``(candidate postings (B, C), match
    mask (B, C))`` for the window; with ``topk == k`` it additionally
    ranks on device and returns
    ``(top postings (B, k), top scores (B, k), top valid (B, k))``.
    With ``doclist=True`` the step returns ``(doc ids (B, C), keep (B, C))``:
    matching positions map to documents through the ``doc_starts`` array in
    ``index`` (identity when absent — non-positional postings are doc ids)
    and duplicates are dropped *on device* by a segment-max scan — matched
    values are sorted within a window, so an entry is the first of its
    document iff its doc id exceeds the running maximum of everything
    before it.  ``probe="kernel"`` routes the inner membership probes
    through the Pallas kernels (interpret mode off-TPU):
    ``anchor_intersect`` tiled compares for the dense layout, plus
    ``fused_decode`` row probes for the fused one (compiled on the TPU,
    interpreted on the CPU; see ``kernels.platform.interpret_mode``).

    ``layout`` selects the device memory model: "dense" reads the
    ``(n_c, expand_len)`` expand tables; "fused" keeps only the compressed
    arrays (anchors + rule-pool pointers, bound ``max_phrase``) in HBM and
    expands each window's entries from the pool inside the step —
    byte-identical results either way.
    """
    phrase = mode == PHRASE
    fused = layout == "fused"
    member = None
    if probe == "kernel":
        from ..kernels.platform import interpret_mode

        interpret = interpret_mode()
        member = (_kernel_member_fused(interpret=interpret) if fused
                  else _kernel_member(interpret=interpret))

    def serve(index: dict, query_terms: jax.Array, query_lens: jax.Array,
              *window: jax.Array | int):
        n = 2 if fused else 1  # the cursor's arguments; the bounds follow
        cursor, steps = window[:n], (window[n:] or (FULL_DEPTH,))[0]
        with jax.named_scope("candidates"):
            if fused:
                idx = _as_compressed(index, max_phrase)
                cand_vals, cand_valid = fused_candidates_for(idx, *cursor)
            else:
                idx = _as_anchored(index)
                cand_vals, cand_valid = candidates_for(idx, query_terms[:, 0],
                                                       *cursor)
        with jax.named_scope("probe"):
            match = _probe_terms(idx, query_terms, query_lens, cand_vals,
                                 cand_valid, max_terms, phrase, member=member,
                                 steps=steps)
        if doclist:
            with jax.named_scope("doclist_dedup"):
                vals = cand_vals - 1
                ds = index.get("doc_starts")
                doc = (vals if ds is None
                       else jnp.searchsorted(ds, vals, side="right") - 1)
                doc = jnp.where(match, doc, -1)
                # cummax, not associative_scan: over a (16, 2^20) window the
                # TPU compiler finishes cummax in seconds and the scan not in
                # 5 min
                prev = jax.lax.cummax(doc, axis=1)
                prev = jnp.concatenate(
                    [jnp.full((doc.shape[0], 1), -1, doc.dtype), prev[:, :-1]],
                    axis=1)
                return doc, match & (doc > prev)
        if not topk:
            return cand_vals - 1, match
        with jax.named_scope("topk"):
            w = _idf_weights(idx, query_terms, query_lens, max_terms, n_docs)
            scores = jnp.where(match, w[:, None], -jnp.inf)
            # stable: ties → lowest index
            top_scores, top_i = jax.lax.top_k(scores, topk)
            top_vals = jnp.take_along_axis(cand_vals - 1, top_i, axis=1)
            return top_vals, top_scores, top_scores > -jnp.inf

    return serve


def make_ranked_step(max_terms: int = 8, topk: int = 10):
    """Batched device BM25 top-k over the scoring-run arrays.

    Geometry: per query slot ``t`` the step gathers that term's padded
    (doc, tf) run row, computes the BM25 contribution against the
    precomputed per-document length norm, and scatter-adds it into a dense
    ``(batch, n_docs)`` score matrix; ``lax.top_k`` then reduces each row
    (ties → lowest doc id: scores are indexed by doc id and ``top_k`` is
    stable).  A zero score means no query term occurs in the doc — BM25
    contributions are strictly positive (log1p idf) — so ``scores > 0`` is
    the validity mask and padding rows never surface.
    """

    def serve(index: dict, query_terms: jax.Array, query_lens: jax.Array,
              *cursor):
        del cursor  # dense scoring has no candidate window to sweep
        b = query_terms.shape[0]
        doc_norm = index["rank_doc_norm"]  # (n_docs,) k1*(1-b+b*dl/avgdl)
        with jax.named_scope("bm25_scatter"):
            scores = jnp.zeros((b, doc_norm.shape[0]), jnp.float32)
            rows = jnp.arange(b)[:, None]
            for t in range(max_terms):
                term = query_terms[:, t]
                docs = index["rank_run_docs"][term]  # (B, Lmax)
                tfs = index["rank_run_tfs"][term]
                live = index["rank_run_valid"][term] & (t < query_lens)[:, None]
                contrib = (index["rank_idf"][term][:, None] * tfs
                           * (BM25_K1 + 1.0) / (tfs + doc_norm[docs]))
                scores = scores.at[rows, docs].add(
                    jnp.where(live, contrib, 0.0))
        with jax.named_scope("topk"):
            top_scores, top_docs = jax.lax.top_k(scores, topk)
        return top_docs, top_scores, top_scores > 0.0

    return serve


# ----------------------------------------------------------------------
# BatchedServer: windowed-exact host driver around the jitted steps
# ----------------------------------------------------------------------
@dataclass
class BatchedServer:
    """Owns the device-resident anchored arrays for one index plus a cache
    of jitted steps, and drives the candidate-window sweep (``_windows``)
    so results are exact for lists of any length.

    ``trace_count`` counts actual jit traces (the counter increments inside
    the traced python body, which only runs on an XLA compile) — the
    retrace metric `Session.metrics` reports.  The ``width`` argument of
    the batched entry points lets the Session pad term matrices to shared
    buckets so equal-shaped traffic reuses one trace."""

    host_index: NonPositionalIndex | PositionalIndex
    arrays: dict[str, jax.Array]
    n_docs: float  # idf denominator (docs, or tokens for positional)
    probe: str = "vmap"  # "vmap" | "kernel" (Pallas anchor_intersect / fused_decode)
    layout: str = "dense"  # "dense" (expand tables) | "fused" (decode-on-device)
    max_phrase: int = 0  # fused layout's static decode bound (longest rule)
    #: device-step kinds this server can run (Session routes through this)
    kinds: frozenset = SERVER_KINDS
    _steps: dict = field(default_factory=dict)
    trace_events: int = 0
    # host-side copies of the immutable planning arrays, so encode /
    # window counting / lane counting never does a device->host transfer
    # per batch
    _lengths_np: np.ndarray | None = None
    _c_offsets_np: np.ndarray | None = None
    _lane_cum_np: np.ndarray | None = None
    _longest_np: np.ndarray | None = None  # fused: each list's longest entry

    def __post_init__(self):
        if self._lengths_np is None:
            self._lengths_np = np.asarray(self.arrays["lengths"])
        if self._c_offsets_np is None:
            self._c_offsets_np = np.asarray(self.arrays["c_offsets"])
        if self._lane_cum_np is None:
            self._lane_cum_np = lane_cumsum(self.arrays)
        if self._longest_np is None and self.layout == "fused":
            self._longest_np = longest_entry(self._c_offsets_np,
                                             self._lane_cum_np)

    #: posting-layout array names (device-memory accounting; rank_* and
    #: doc_starts are layout-independent extras)
    _LAYOUT_ARRAYS = {
        "dense": ("anchors", "c_offsets", "expand", "expand_valid", "lengths"),
        "fused": ("anchors", "c_offsets", "c_ptr", "c_len", "pool", "lengths"),
    }

    @classmethod
    def from_index(cls, index: NonPositionalIndex | PositionalIndex,
                   expand_len: int = 32, probe: str = "vmap",
                   layout: str = "auto") -> "BatchedServer":
        store = index.store
        resident = CAP_DEVICE_RESIDENT in capabilities_of(store)
        if layout == "auto":
            # device-resident (Re-Pair) stores ship their compressed arrays
            # to HBM and decode inside the sweep; everything else re-anchors
            # into the dense expand tables as before
            layout = "fused" if resident else "dense"
        if layout not in cls._LAYOUT_ARRAYS:
            raise ValueError(f"unknown layout {layout!r}")
        max_phrase = 0
        if layout == "fused":
            if resident:  # the backend's own grammar compresses directly
                cidx = CompressedAnchoredIndex.from_store(store)
            else:  # re-compress from decoded lists (any registered backend)
                lists = [store.get_list(i) for i in range(store.n_lists)]
                cidx = build_compressed_anchored(lists)
            arrays = {"anchors": cidx.anchors, "c_offsets": cidx.c_offsets,
                      "c_ptr": cidx.c_ptr, "c_len": cidx.c_len,
                      "pool": cidx.pool, "lengths": cidx.lengths}
            max_phrase = cidx.max_phrase
        elif resident:
            # the backend's own arrays anchor directly (no decode pass)
            aidx = AnchoredIndex.from_store(store, expand_len=expand_len)
            arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
                      "expand": aidx.expand, "expand_valid": aidx.expand_valid,
                      "lengths": aidx.lengths}
        else:  # re-anchor from decoded lists (any registered backend)
            lists = [store.get_list(i) for i in range(store.n_lists)]
            aidx = build_anchored(lists, expand_len=expand_len)
            arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
                      "expand": aidx.expand, "expand_valid": aidx.expand_valid,
                      "lengths": aidx.lengths}
        if isinstance(index, PositionalIndex):
            # device-side position -> document mapping for doc listing
            arrays["doc_starts"] = jnp.asarray(index.doc_starts, jnp.int32)
        kinds = SERVER_KINDS
        scoring = getattr(index, "scoring", None)
        if isinstance(index, NonPositionalIndex) and scoring is not None:
            # scoring runs as padded dense matrices: row per term, one
            # (doc, tf) slot per posting — the device ranked step gathers
            # rows, scatter-adds BM25 contributions, reduces with top_k
            n_lists = len(scoring.max_tf)
            n_docs = scoring.n_docs
            lens = np.diff(scoring.run_offsets)
            lmax = max(1, int(lens.max()) if n_lists else 1)
            run_docs = np.zeros((n_lists, lmax), np.int32)
            run_tfs = np.zeros((n_lists, lmax), np.float32)
            run_valid = np.zeros((n_lists, lmax), bool)
            for i in range(n_lists):
                d, tf = scoring.term_runs(i)
                run_docs[i, : len(d)] = d
                run_tfs[i, : len(tf)] = tf
                run_valid[i, : len(d)] = True
            dl = scoring.doc_lengths.astype(np.float32)
            avgdl = max(scoring.avgdl, 1e-9)
            arrays["rank_run_docs"] = jnp.asarray(run_docs)
            arrays["rank_run_tfs"] = jnp.asarray(run_tfs)
            arrays["rank_run_valid"] = jnp.asarray(run_valid)
            arrays["rank_doc_norm"] = jnp.asarray(
                BM25_K1 * (1.0 - BM25_B + BM25_B * dl / avgdl), jnp.float32)
            arrays["rank_idf"] = jnp.asarray(
                [bm25_idf(int(ell), n_docs) for ell in lens], jnp.float32
            ).reshape(n_lists)
            kinds = SERVER_KINDS | {RANK}
        return cls(host_index=index, arrays=arrays,
                   n_docs=float(index.universe_size), probe=probe,
                   layout=layout, max_phrase=max_phrase, kinds=kinds)

    @property
    def trace_count(self) -> int:
        return self.trace_events

    def device_bytes(self) -> int:
        """HBM bytes of the posting-layout arrays (the quantity the fused
        layout shrinks; rank/doc-mapping extras are layout-independent)."""
        return sum(self.arrays[k].size * self.arrays[k].dtype.itemsize
                   for k in self._LAYOUT_ARRAYS[self.layout])

    def c_entries(self, list_id: int) -> int:
        """C-entry count of one list (window-sweep length; cost model)."""
        c = self._c_offsets_np
        return int(c[list_id + 1] - c[list_id])

    # -- encoding -------------------------------------------------------
    def encode(self, queries: list[list[str]], sort_by_length: bool = False,
               width: int | None = None) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """See :func:`encode_queries` (the shared driver encode step)."""
        with tracing.span("engine.encode"):
            return encode_queries(self.host_index, self._lengths_np, queries,
                                  sort_by_length=sort_by_length, width=width)

    def _step(self, kind: str, width: int, topk: int = 0, doclist: bool = False):
        key = (kind, width, topk, doclist)
        if key not in self._steps:
            if kind == RANK:
                raw = make_ranked_step(max_terms=width, topk=topk)
            else:
                mode = PHRASE if kind == PHRASE else AND
                raw = make_serve_step(max_terms=width, mode=mode, topk=topk,
                                      n_docs=self.n_docs, probe=self.probe,
                                      doclist=doclist, layout=self.layout,
                                      max_phrase=self.max_phrase)

            def counted(index, query_terms, query_lens, *cursor):
                # this body runs only while jax traces (i.e. on a compile),
                # so the increment counts actual retraces
                self.trace_events += 1
                return raw(index, query_terms, query_lens, *cursor)

            # the name of the XLA module, which the device trace shows
            counted.__name__ = counted.__qualname__ = step_name(kind, topk,
                                                                doclist)
            self._steps[key] = jax.jit(counted)
        return self._steps[key]

    def _windows(self, qt: np.ndarray, ql: np.ndarray, ok: np.ndarray):
        """The sweep's windows over each query's driving list ``qt[:, 0]``,
        at least one: ``(cursor, counts)`` per step call, from host copies
        only.  The cursor ends with the window's search bounds (see
        :meth:`_bounds`); ``counts`` are its ``engine.window`` attrs.

        Dense layout: MAX_CAND_ROWS expand rows of every list per window.
        Fused layout: each query takes the C entries, from where its last
        window stopped, whose postings fit ``window_lanes(max_phrase)``
        lanes; a query with ``ok`` False or an exhausted list takes an
        empty range."""
        b, first = qt.shape[0], qt[:, 0]
        c_off, cum = self._c_offsets_np, self._lane_cum_np
        if self.layout != "fused":
            per_row = int(self.arrays["expand"].shape[-1])
            drive = first[ok] if ok.any() else first[:1]
            rows = int((c_off[drive + 1] - c_off[drive]).max())
            for w in range(max(1, -(-rows // MAX_CAND_ROWS))):
                start = w * MAX_CAND_ROWS
                steps, counts = self._bounds(
                    qt, ql, ok & (c_off[first + 1] - c_off[first] > start))
                yield (start, steps), dict(
                    lanes=b * MAX_CAND_ROWS * per_row,
                    live_lanes=window_live_lanes(c_off, cum, first[ok], start),
                    **counts)
            return
        lanes = window_lanes(self.max_phrase)
        lo = np.where(ok, c_off[first], 0).astype(np.int64)
        hi = np.where(ok, c_off[first + 1], 0).astype(np.int64)
        while True:
            # the furthest entry whose postings since lo fit the lanes
            reach = np.searchsorted(cum, cum[lo] + lanes, side="right") - 1
            end = np.minimum(np.minimum(reach, hi), lo + lanes)
            steps, counts = self._bounds(qt, ql, end > lo)
            yield (lo.astype(np.int32), end.astype(np.int32), steps), dict(
                lanes=b * lanes, live_lanes=int((cum[end] - cum[lo]).sum()),
                **counts)
            lo = end
            if not (lo < hi).any():
                return

    def _bounds(self, qt: np.ndarray, ql: np.ndarray,
                live: np.ndarray) -> tuple[np.ndarray, dict]:
        """A window's search bounds, for the step (:func:`search_steps` over
        the queries ``live`` in it), and as the ``anchor_steps`` and
        ``pool_steps`` its span records.  The Pallas probe runs no
        halvings: its span records none."""
        steps = search_steps(self._c_offsets_np, self._longest_np, qt, ql, live)
        n = steps if self.probe != "kernel" else np.zeros_like(steps)
        return steps, dict(anchor_steps=int(n[0]), pool_steps=int(n[1]))

    def _n_windows(self, qt: np.ndarray, ql: np.ndarray, ok: np.ndarray) -> int:
        """Step calls a sweep over the batch makes (topk may stop sooner)."""
        return sum(1 for _ in self._windows(qt, ql, ok))

    def _sweep(self, kind: str, queries: list[list[str]],
               width: int | None = None) -> list[np.ndarray]:
        qt, ql, ok = self.encode(queries, sort_by_length=(kind != PHRASE),
                                 width=width)
        step = self._step(kind, qt.shape[1])
        hits: list[list[np.ndarray]] = [[] for _ in queries]
        for w, (cursor, counts) in enumerate(self._windows(qt, ql, ok)):
            with step_window(self, step, self.arrays, qt, ql, w, cursor,
                             counts) as (vals, mask):
                for i in range(len(queries)):
                    if ok[i]:
                        hits[i].append(vals[i][mask[i]])
        return _merge(hits, ok)

    # -- public batched entry points ------------------------------------
    def conjunctive(self, queries: list[list[str]],
                    width: int | None = None) -> list[np.ndarray]:
        """Batched AND: sorted doc ids per query, exact for any list length."""
        return self._sweep(AND, queries, width=width)

    def phrase(self, queries: list[list[str]],
               width: int | None = None) -> list[np.ndarray]:
        """Batched phrase: sorted start positions per query (positional
        index).  Use ``positions_to_docs`` on the host index for (doc, off)."""
        return self._sweep(PHRASE, queries, width=width)

    def doclist(self, queries: list[list[str]], phrase: bool = False,
                width: int | None = None) -> list[np.ndarray]:
        """Batched document listing: sorted distinct doc ids per query.

        The position->document mapping and the per-window dedup (segment-max
        over candidate doc ids) run *inside* the jitted step, so only the
        distinct survivors of each window cross back to the host, which
        unions them across windows — exact for lists of any length."""
        kind = PHRASE if phrase else AND
        qt, ql, ok = self.encode(queries, sort_by_length=not phrase, width=width)
        step = self._step(kind, qt.shape[1], doclist=True)
        hits: list[list[np.ndarray]] = [[] for _ in queries]
        for w, (cursor, counts) in enumerate(self._windows(qt, ql, ok)):
            with step_window(self, step, self.arrays, qt, ql, w, cursor,
                             counts) as (docs, keep):
                for i in range(len(queries)):
                    if ok[i]:
                        hits[i].append(docs[i][keep[i]])
        return _merge(hits, ok)

    def topk(self, queries: list[list[str]], k: int = 10,
             width: int | None = None) -> list[np.ndarray]:
        """Batched ranked AND: first k matches under the idf-proxy weight
        (matches the host ``ranked_and`` order).  Ranking runs on device;
        the window sweep stops as soon as every query has k hits."""
        qt, ql, ok = self.encode(queries, sort_by_length=True, width=width)
        step = self._step(AND, qt.shape[1], topk=int(k))
        got: list[list[np.ndarray]] = [[] for _ in queries]
        counts = np.zeros(len(queries), np.int64)
        for w, (cursor, attrs) in enumerate(self._windows(qt, ql, ok)):
            with step_window(self, step, self.arrays, qt, ql, w, cursor, attrs,
                             fetch=(0, 2)) as (vals, valid):
                for i in range(len(queries)):
                    if ok[i]:
                        got[i].append(vals[i][valid[i]])
                counts[ok] += valid[ok].sum(axis=1)
            if (counts >= k)[ok].all():
                break
        with tracing.span("engine.merge"):
            empty = np.zeros(0, np.int64)
            return [np.concatenate(g)[:k].astype(np.int64) if (o and g) else empty
                    for g, o in zip(got, ok)]

    def ranked(self, queries: list[list[str]], k: int = 10,
               width: int | None = None) -> list[np.ndarray]:
        """Batched BM25 ranked disjunction: top-``k`` doc ids per query,
        scored and reduced on device (see :func:`make_ranked_step`).  One
        step covers the whole collection — dense scoring has no candidate
        window — so a warmed (width, k) shape never retraces."""
        if "rank_doc_norm" not in self.arrays:
            raise ValueError(
                f"this server holds no scoring arrays "
                f"({self.host_index.store_name!r}): rebuild the index with "
                f"scoring statistics to serve rank queries on device")
        # duplicate query terms would scatter-add twice; the host scorer
        # dedups, so dedup here for identical answers
        queries = [list(dict.fromkeys(q)) for q in queries]
        qt, ql, ok = self.encode(queries, width=width)
        eff_k = min(int(k), int(self.arrays["rank_doc_norm"].shape[0]))
        step = self._step(RANK, qt.shape[1], topk=eff_k)
        # lanes: the (doc, tf) run slots the step gathers; live: the
        # postings of the active queries' terms
        active = np.arange(qt.shape[1])[None, :] < ql[:, None]
        counts = dict(
            lanes=qt.size * int(self.arrays["rank_run_docs"].shape[1]),
            live_lanes=int(self._lengths_np[qt][active & ok[:, None]].sum()),
            anchor_steps=0, pool_steps=0)
        with step_window(self, step, self.arrays, qt, ql, 0, (), counts,
                         fetch=(0, 2)) as (docs, valid):
            empty = np.zeros(0, np.int64)
            return [docs[i][valid[i]].astype(np.int64) if ok[i] else empty
                    for i in range(len(queries))]


@contextmanager
def step_window(server, step, arrays: dict, qt: np.ndarray, ql: np.ndarray,
                w: int, cursor: tuple, counts: dict,
                fetch: tuple[int, ...] = (0, 1)):
    """Run one call of ``server``'s ``step`` over sweep window ``w``, whose
    position (and search bounds) the step's ``cursor`` arguments give,
    under an ``engine.window`` span and yield the host copies of its
    outputs ``fetch``; the caller's per-query masking runs inside the
    ``engine.fetch`` span.  ``counts`` become the span's attrs (``lanes``,
    ``live_lanes``, ``anchor_steps``, ``pool_steps``)."""
    traces = server.trace_events
    with tracing.span("engine.window", program=step.__name__, window=w,
                      **counts) as sp:
        outs = step(arrays, jnp.asarray(qt), jnp.asarray(ql), *cursor)
        sp.set(traced=int(server.trace_events > traces))
        outs = [outs[i] for i in fetch]
        with tracing.span("engine.device_wait"):
            jax.block_until_ready(outs)
        with tracing.span("engine.fetch") as f:
            host = [np.asarray(o) for o in outs]
            f.set(bytes=sum(h.nbytes for h in host))
            yield host


def step_name(kind: str, topk: int = 0, doclist: bool = False) -> str:
    """The name of a jitted step, after what it serves: ``serve_and``,
    ``serve_phrase``, ``serve_and_docs``, ``serve_phrase_docs``,
    ``serve_and_top``, ``rank_bm25``."""
    if kind == RANK:
        return "rank_bm25"
    return (f"serve_{PHRASE if kind == PHRASE else AND}"
            + ("_docs" if doclist else "") + ("_top" if topk else ""))


def lane_cumsum(arrays: dict) -> np.ndarray:
    """Host copy of the decoded postings up to each C entry (a leading
    shard axis is kept): ``c_len`` on the fused layout, the valid lanes of
    each expand row on the dense one.  Made once, at construction."""
    rows = (np.asarray(arrays["c_len"]) if "c_len" in arrays
            else np.asarray(arrays["expand_valid"]).sum(axis=-1))
    cum = np.zeros(rows.shape[:-1] + (rows.shape[-1] + 1,), np.int64)
    np.cumsum(rows, axis=-1, out=cum[..., 1:])
    return cum


def longest_entry(c_offsets: np.ndarray, lane_cum: np.ndarray) -> np.ndarray:
    """Host copy of the postings of each list's longest C entry (0 for an
    empty list), from :func:`lane_cumsum`.  Made once, at construction."""
    sizes = np.diff(lane_cum)
    out = np.zeros(len(c_offsets) - 1, np.int64)
    full = c_offsets[1:] > c_offsets[:-1]
    if full.any():
        # the empty lists between two full ones hold no entry, so each
        # segment from one full list's start to the next is that list
        out[full] = np.maximum.reduceat(sizes, c_offsets[:-1][full])
    return out


def search_steps(c_offsets: np.ndarray, longest: np.ndarray | None,
                 qt: np.ndarray, ql: np.ndarray, live: np.ndarray) -> np.ndarray:
    """The halvings a window's two binary searches run, int32[2], reckoned
    once on the host for all lanes: the ``int.bit_length`` of the most C
    entries of a probed list (anchor search), and of the most postings of
    one entry of a probed list (pool search; ``longest`` is None on the
    dense layout, which has none).  The probed lists are term slots 1 ..
    ``ql`` - 1 of the queries ``live`` in the window, so padding slots and
    queries without a candidate raise neither count.  A lower-bound search
    over n entries has converged after ``n.bit_length()`` halvings, so the
    live lanes answer as under FULL_DEPTH; the others' answers are
    discarded.  An int32 host array: the step gets it like ``qt``, with no
    conversion program of its own."""
    slot = np.arange(qt.shape[1])[None, :]
    ids = qt[(slot >= 1) & (slot < ql[:, None]) & live[:, None]]
    widest = int((c_offsets[ids + 1] - c_offsets[ids]).max(initial=0))
    entry = 0 if longest is None else int(longest[ids].max(initial=0))
    return np.minimum([widest.bit_length(), entry.bit_length()],
                      MAX_SEARCH_STEPS).astype(np.int32)


def window_live_lanes(c_offsets: np.ndarray, lane_cum: np.ndarray,
                      first: np.ndarray, row_start: int) -> int:
    """Decoded postings in the candidate window at ``row_start`` of the
    lists ``first``: their C entries [row_start, row_start + MAX_CAND_ROWS)
    that exist.  With a leading shard axis on ``c_offsets`` and
    ``lane_cum`` the count sums over the shards."""
    lo = c_offsets[..., first]
    hi = c_offsets[..., first + 1]
    a = np.minimum(lo + row_start, hi)
    b = np.minimum(lo + row_start + MAX_CAND_ROWS, hi)
    return int((np.take_along_axis(lane_cum, b, -1)
                - np.take_along_axis(lane_cum, a, -1)).sum())


def _merge(hits: list[list[np.ndarray]], ok: np.ndarray) -> list[np.ndarray]:
    """The per-batch union of each query's window answers."""
    with tracing.span("engine.merge"):
        empty = np.zeros(0, np.int64)
        return [np.unique(np.concatenate(h)).astype(np.int64) if (o and h)
                else empty for h, o in zip(hits, ok)]
