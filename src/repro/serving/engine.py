"""Batched device serving + legacy engine shims.

The serving stack is now plan-first (PR 4):

* ``serving.plan`` — `parse_query` → logical plan → cost-aware compiler →
  physical plan (`route_query` / `compile_query` / EXPLAIN rendering).
* ``serving.session.Session`` — the **only** entry point: plan-cached,
  jit-bucket-grouped `execute`, plus `explain` and `metrics`.
* this module — the device-side batched steps (:func:`make_serve_step`),
  the windowed-exact device driver (:class:`BatchedServer`), and thin
  **deprecation shims** (:class:`QueryEngine`, :class:`QueryPlanner`) that
  keep the old per-kind call sites working for one PR.

Device-step geometry: padded (batch, width) term-id matrices; each step
generates candidates from the query's first list via the bounded expansion
table and probes the remaining terms through the anchored binary search
(``member_batch``).  Phrase queries probe *shifted* candidates
(offset-shifted intersection, paper §3): term ``t`` of a phrase must hold
``position + t``.  Candidate generation is **windowed**: the host driver
sweeps ``row_start`` over the driving list's C-entries so arbitrarily long
lists are served exactly.  Ranked top-k computes idf-proxy weights on
device and reduces with ``lax.top_k``; document listing maps matches to
doc ids and dedups on device with a segment-max scan.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.anchors import (
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
from .plan import (  # noqa: F401  (re-exported: the legacy import surface)
    AND,
    DOCS,
    DOCS_TOPK,
    MAX_CAND_ROWS,
    PHRASE,
    RANK,
    SERVER_KINDS,
    TOPK,
    WORD,
    ParsedQuery,
    parse_query,
    route_query,
    width_bucket,
)
from .session import Session


@dataclass(frozen=True)
class QueryPlan:
    """Legacy plan record (the pre-IR surface): see ``serving.plan.Route``
    and ``Session.explain`` for the first-class replacement."""

    query: ParsedQuery
    index: str  # "nonpositional" | "positional"
    route: str  # "host" | "device"
    strategy: str  # host physical operator or device step name


class QueryPlanner:
    """Deprecated routing shim: ``plan`` wraps the plan compiler's
    :func:`repro.serving.plan.route_query` decision into the legacy
    :class:`QueryPlan` record.  Use ``Session.explain`` / ``Session.plan``."""

    def __init__(self, engine: "QueryEngine"):
        self.engine = engine

    def plan(self, q, prefer_device: bool = True) -> QueryPlan:
        pq = parse_query(q)
        rt = route_query(self.engine, pq, prefer_device=prefer_device)
        return QueryPlan(pq, rt.index, rt.route, rt.strategy)


# ----------------------------------------------------------------------
# legacy host engine (deprecation shim over Session)
# ----------------------------------------------------------------------
_DEPRECATION_WARNED = False


def _warn_deprecated(method: str) -> None:
    global _DEPRECATION_WARNED
    if not _DEPRECATION_WARNED:
        _DEPRECATION_WARNED = True
        warnings.warn(
            f"QueryEngine.{method} (and the other per-kind QueryEngine "
            f"methods) are deprecated: build a repro.serving.session.Session "
            f"and go through Session.execute / Session.explain",
            DeprecationWarning, stacklevel=3)


@dataclass
class QueryEngine:
    """Deprecated facade: every call delegates to an owned
    :class:`~repro.serving.session.Session` (``.session``).  ``execute`` /
    ``batch`` stay silent for migration; the per-kind methods emit one
    ``DeprecationWarning`` per process."""

    # a positional-only engine (index=None) still serves phrase and document
    # listing queries through the doc-run / grammar structures
    index: NonPositionalIndex | None
    positional: PositionalIndex | None = None
    server: "BatchedServer | None" = None  # device path over `index`
    positional_server: "BatchedServer | None" = None  # device path over `positional`

    def __post_init__(self):
        self.session = Session(index=self.index, positional=self.positional,
                               server=self.server,
                               positional_server=self.positional_server)
        self.planner = QueryPlanner(self)

    def __setattr__(self, name, value):
        # keep the owned Session live: old call sites attach servers (or swap
        # indexes) after construction, and routes planned under the previous
        # configuration must not be served from the cache
        object.__setattr__(self, name, value)
        if (name in ("index", "positional", "server", "positional_server")
                and getattr(self, "session", None) is not None):
            setattr(self.session, name, value)
            self.session._plan_cache.clear()

    def execute(self, q) -> np.ndarray:
        """Plan and run one query (a list of words is the legacy AND form)."""
        return self.session.execute(parse_query(q))

    def batch(self, queries: list) -> list[np.ndarray]:
        """Serve a mixed batch in original order (see ``Session.execute``)."""
        return self.session.execute(list(queries))

    def doc_runs(self):
        return self.session.doc_runs()

    # -- deprecated per-kind surface ------------------------------------
    def word(self, w: str) -> np.ndarray:
        _warn_deprecated("word")
        return self.session._word(w)

    def conjunctive(self, words: list[str]) -> np.ndarray:
        _warn_deprecated("conjunctive")
        return self.session._conjunctive(words)

    and_ = conjunctive

    def phrase(self, tokens: list[str]) -> np.ndarray:
        _warn_deprecated("phrase")
        return self.session._phrase(tokens)

    def ranked_and(self, words: list[str], k: int = 10) -> np.ndarray:
        _warn_deprecated("ranked_and")
        return self.session._ranked_and(words, k=k)

    topk = ranked_and

    def doc_list(self, terms: list[str], phrase: bool = False) -> np.ndarray:
        _warn_deprecated("doc_list")
        return self.session._doc_list(terms, phrase=phrase)

    def doc_topk(self, terms: list[str], k: int = 10, phrase: bool = False) -> np.ndarray:
        _warn_deprecated("doc_topk")
        return self.session._doc_topk(terms, k=k, phrase=phrase)


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


def fused_candidates_for(idx: CompressedAnchoredIndex, list_ids: jax.Array,
                         row_start: jax.Array | int = 0,
                         decode=None) -> tuple[jax.Array, jax.Array]:
    """Fused-layout counterpart of :func:`candidates_for`: the same
    MAX_CAND_ROWS window, but each C entry decodes from the shared
    prefix-summed pool (bounded by ``max_phrase``) instead of reading a
    dense expand row.

    ``decode`` swaps the decode implementation (inline anchor re-base by
    default; the Pallas ``fused_decode`` kernel via ``probe="kernel"``).
    Returns (values (B, C), valid (B, C)) in cumulative-gap space —
    identical to the dense generator's output for the same store.
    """
    lo = idx.c_offsets[list_ids] + row_start
    hi = idx.c_offsets[list_ids + 1]
    rows = lo[:, None] + jnp.arange(MAX_CAND_ROWS)[None, :]
    valid_rows = rows < hi[:, None]
    rows = jnp.minimum(rows, idx.anchors.shape[0] - 1)
    flat = rows.reshape(-1)
    L = max(int(idx.max_phrase), 1)
    base = idx.anchors[flat]
    lens = jnp.where(valid_rows.reshape(-1), idx.c_len[flat], 0)
    # (B*ROWS, L) contiguous prefix-sum row slices from the padded pool
    # (the ragged gather stays outside the kernel)
    psums = jax.vmap(
        lambda p: jax.lax.dynamic_slice_in_dim(idx.pool, p, L)
    )(idx.c_ptr[flat])
    if decode is None:
        valid = jnp.arange(L, dtype=jnp.int32)[None, :] < lens[:, None]
        vals = base[:, None] + psums
    else:
        vals, valid = decode(psums, base, lens)
    b = list_ids.shape[0]
    return vals.reshape(b, -1), valid.reshape(b, -1)


def _probe_terms(idx, query_terms, query_lens, cand_vals, cand_valid,
                 max_terms: int, phrase: bool, member=None):
    """AND / phrase probe loop shared by all steps.  For phrase queries term
    ``t`` probes candidate + t (offset-shifted intersection, §3).  ``member``
    swaps the probe implementation (vmapped binary search by default —
    picked by index layout — or the Pallas kernels via ``probe="kernel"``)."""
    if member is None:
        member = (member_batch_compressed
                  if isinstance(idx, CompressedAnchoredIndex) else member_batch)
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
    positional probes).  With ``topk == 0`` the step returns
    ``(candidate postings (B, C), match mask (B, C))`` for the window at
    ``row_start``; with ``topk == k`` it additionally ranks on device and
    returns ``(top postings (B, k), top scores (B, k), top valid (B, k))``.
    With ``doclist=True`` the step returns ``(doc ids (B, C), keep (B, C))``:
    matching positions map to documents through the ``doc_starts`` array in
    ``index`` (identity when absent — non-positional postings are doc ids)
    and duplicates are dropped *on device* by a segment-max scan — matched
    values are sorted within a window, so an entry is the first of its
    document iff its doc id exceeds the running maximum of everything
    before it.  ``probe="kernel"`` routes the inner membership probes
    through the Pallas kernels (interpret mode off-TPU):
    ``anchor_intersect`` tiled compares for the dense layout, plus
    ``fused_decode`` expansion for the fused one (compiled on the TPU,
    interpreted on the CPU; see ``kernels.platform.interpret_mode``).

    ``layout`` selects the device memory model: "dense" reads the
    ``(n_c, expand_len)`` expand tables; "fused" keeps only the compressed
    arrays (anchors + rule-pool pointers, bound ``max_phrase``) in HBM and
    decodes inside the sweep — byte-identical results either way.
    """
    phrase = mode == PHRASE
    fused = layout == "fused"
    member = None
    decode = None
    if probe == "kernel":
        from ..kernels.platform import interpret_mode

        interpret = interpret_mode()
        if fused:
            from ..kernels.fused_decode.ops import decode_rows

            member = _kernel_member_fused(interpret=interpret)
            decode = lambda g, b, n: decode_rows(g, b, n, interpret=interpret)
        else:
            member = _kernel_member(interpret=interpret)

    def serve(index: dict, query_terms: jax.Array, query_lens: jax.Array,
              row_start: jax.Array | int = 0):
        if fused:
            idx = _as_compressed(index, max_phrase)
            cand_vals, cand_valid = fused_candidates_for(
                idx, query_terms[:, 0], row_start, decode=decode)
        else:
            idx = _as_anchored(index)
            cand_vals, cand_valid = candidates_for(idx, query_terms[:, 0], row_start)
        match = _probe_terms(idx, query_terms, query_lens, cand_vals, cand_valid,
                             max_terms, phrase, member=member)
        if doclist:
            vals = cand_vals - 1
            ds = index.get("doc_starts")
            doc = vals if ds is None else jnp.searchsorted(ds, vals, side="right") - 1
            doc = jnp.where(match, doc, -1)
            # cummax, not associative_scan: over a (16, 2^20) window the TPU
            # compiler finishes cummax in seconds and the scan not in 5 min
            prev = jax.lax.cummax(doc, axis=1)
            prev = jnp.concatenate(
                [jnp.full((doc.shape[0], 1), -1, doc.dtype), prev[:, :-1]], axis=1)
            return doc, match & (doc > prev)
        if not topk:
            return cand_vals - 1, match
        w = _idf_weights(idx, query_terms, query_lens, max_terms, n_docs)
        scores = jnp.where(match, w[:, None], -jnp.inf)
        top_scores, top_i = jax.lax.top_k(scores, topk)  # stable: ties → lowest index
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
              row_start: jax.Array | int = 0):
        del row_start  # dense scoring has no candidate window to sweep
        b = query_terms.shape[0]
        doc_norm = index["rank_doc_norm"]  # (n_docs,) k1*(1-b+b*dl/avgdl)
        scores = jnp.zeros((b, doc_norm.shape[0]), jnp.float32)
        rows = jnp.arange(b)[:, None]
        for t in range(max_terms):
            term = query_terms[:, t]
            docs = index["rank_run_docs"][term]  # (B, Lmax)
            tfs = index["rank_run_tfs"][term]
            live = index["rank_run_valid"][term] & (t < query_lens)[:, None]
            contrib = (index["rank_idf"][term][:, None] * tfs * (BM25_K1 + 1.0)
                       / (tfs + doc_norm[docs]))
            scores = scores.at[rows, docs].add(jnp.where(live, contrib, 0.0))
        top_scores, top_docs = jax.lax.top_k(scores, topk)
        return top_docs, top_scores, top_scores > 0.0

    return serve


def make_uihrdc_serve_step(max_terms: int = 8):
    """The AND-only step of the ``uihrdc`` dry-run arch (kept as the
    compiled entry point; see :func:`make_serve_step` for phrase/top-k)."""
    return make_serve_step(max_terms=max_terms, mode=AND)


# ----------------------------------------------------------------------
# BatchedServer: windowed-exact host driver around the jitted steps
# ----------------------------------------------------------------------
@dataclass
class BatchedServer:
    """Owns the device-resident anchored arrays for one index plus a cache
    of jitted steps, and drives the candidate-window sweep so results are
    exact for lists of any length (no 64-candidate truncation).

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
    # window counting never does a device->host transfer per batch
    _lengths_np: np.ndarray | None = None
    _c_offsets_np: np.ndarray | None = None

    def __post_init__(self):
        if self._lengths_np is None:
            self._lengths_np = np.asarray(self.arrays["lengths"])
        if self._c_offsets_np is None:
            self._c_offsets_np = np.asarray(self.arrays["c_offsets"])

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

            def counted(index, query_terms, query_lens, row_start=0, _raw=raw):
                # this body runs only while jax traces (i.e. on a compile),
                # so the increment counts actual retraces
                self.trace_events += 1
                return _raw(index, query_terms, query_lens, row_start)

            self._steps[key] = jax.jit(counted)
        return self._steps[key]

    def _n_windows(self, qt: np.ndarray, ok: np.ndarray) -> int:
        c_off = self._c_offsets_np
        first = qt[:, 0][ok] if ok.any() else qt[:1, 0]
        rows = c_off[first + 1] - c_off[first]
        return max(1, int(-(-int(rows.max()) // MAX_CAND_ROWS)))

    def _sweep(self, kind: str, queries: list[list[str]],
               width: int | None = None) -> list[np.ndarray]:
        qt, ql, ok = self.encode(queries, sort_by_length=(kind != PHRASE),
                                 width=width)
        step = self._step(kind, qt.shape[1])
        hits: list[list[np.ndarray]] = [[] for _ in queries]
        for w in range(self._n_windows(qt, ok)):
            vals, mask = step(self.arrays, jnp.asarray(qt), jnp.asarray(ql),
                              w * MAX_CAND_ROWS)
            vals, mask = np.asarray(vals), np.asarray(mask)
            for i in range(len(queries)):
                if ok[i]:
                    hits[i].append(vals[i][mask[i]])
        empty = np.zeros(0, np.int64)
        return [np.unique(np.concatenate(h)).astype(np.int64) if (o and h) else empty
                for h, o in zip(hits, ok)]

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
        for w in range(self._n_windows(qt, ok)):
            docs, keep = step(self.arrays, jnp.asarray(qt), jnp.asarray(ql),
                              w * MAX_CAND_ROWS)
            docs, keep = np.asarray(docs), np.asarray(keep)
            for i in range(len(queries)):
                if ok[i]:
                    hits[i].append(docs[i][keep[i]])
        empty = np.zeros(0, np.int64)
        return [np.unique(np.concatenate(h)).astype(np.int64) if (o and h) else empty
                for h, o in zip(hits, ok)]

    def topk(self, queries: list[list[str]], k: int = 10,
             width: int | None = None) -> list[np.ndarray]:
        """Batched ranked AND: first k matches under the idf-proxy weight
        (matches the host ``ranked_and`` order).  Ranking runs on device;
        the window sweep stops as soon as every query has k hits."""
        qt, ql, ok = self.encode(queries, sort_by_length=True, width=width)
        step = self._step(AND, qt.shape[1], topk=int(k))
        got: list[list[np.ndarray]] = [[] for _ in queries]
        counts = np.zeros(len(queries), np.int64)
        for w in range(self._n_windows(qt, ok)):
            vals, scores, valid = step(self.arrays, jnp.asarray(qt), jnp.asarray(ql),
                                       w * MAX_CAND_ROWS)
            vals, valid = np.asarray(vals), np.asarray(valid)
            for i in range(len(queries)):
                if ok[i]:
                    got[i].append(vals[i][valid[i]])
            counts[ok] += valid[ok].sum(axis=1)
            if (counts >= k)[ok].all():
                break
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
        docs, _scores, valid = step(self.arrays, jnp.asarray(qt), jnp.asarray(ql))
        docs, valid = np.asarray(docs), np.asarray(valid)
        empty = np.zeros(0, np.int64)
        return [docs[i][valid[i]].astype(np.int64) if ok[i] else empty
                for i in range(len(queries))]
