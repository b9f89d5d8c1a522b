"""Document-partitioned anchored index (§Perf H5 iter 2 — the production
layout for >10^9-posting deployments, DESIGN.md §4).

Each shard owns the postings of one *document range* (or, for positional
phrase serving, one *position range* cut at document boundaries), re-based
to local ids, with its own anchored Re-Pair arrays.  Per-shard arrays are
padded to a common size and stacked with a leading shard dim; ``shard_map``
runs every probe entirely shard-local (queries replicated, zero collectives
inside), and results come back as (shards, batch, cand) with global ids —
the classic broadcast-query / local-search / merge-results search topology.

Both query kinds of the batched engine run under this layout: conjunctive
AND (mode="and") and offset-shifted phrase probes (mode="phrase"); the
``row_start`` argument is the same candidate-window cursor as in
``engine.candidates_for``, so long per-shard lists are swept exactly.

:class:`PartitionedServer` wraps the sharded layout in the batched-server
protocol (``conjunctive`` / ``phrase`` / ``encode`` / ``trace_count``), so
a ``Session`` can route device traffic onto the shards exactly like onto a
single :class:`~repro.serving.engine.BatchedServer` — it declares
``kinds = {"and", "phrase"}`` and the plan compiler keeps top-k and doc
listing on the host.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.anchors import AnchoredIndex, build_anchored
from . import tracing
from .engine import (
    MAX_CAND_ROWS,
    _merge,
    _probe_terms,
    candidates_for,
    encode_queries,
    lane_cumsum,
    step_window,
    window_live_lanes,
)


@dataclass
class PartitionedAnchoredIndex:
    arrays: dict[str, jax.Array]  # each with leading (n_shards,) dim
    doc_bounds: np.ndarray  # (n_shards + 1,) global doc-range boundaries
    n_shards: int
    expand_len: int

    @classmethod
    def build(cls, lists: list[np.ndarray], n_docs: int, n_shards: int,
              bounds: np.ndarray | None = None, mesh=None,
              shard_axis: str = "data", **kw) -> "PartitionedAnchoredIndex":
        """``bounds`` overrides the equal-width split — pass document-start
        positions for a positional index so phrases never span shards.
        With a ``mesh`` each shard's slice is placed once on its own
        device along ``shard_axis`` (otherwise everything lands on the
        default device)."""
        if bounds is None:
            bounds = np.linspace(0, n_docs, n_shards + 1).astype(np.int64)
        else:
            bounds = np.asarray(bounds, dtype=np.int64)
            assert len(bounds) == n_shards + 1
        shards: list[AnchoredIndex] = []
        for s in range(n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            local = []
            for l in lists:
                seg = l[(l >= lo) & (l < hi)] - lo  # re-based to local ids
                local.append(seg if len(seg) else np.asarray([], dtype=np.int64))
            shards.append(build_anchored(local, **kw))
        # pad to common sizes and stack
        max_nc = max(int(a.anchors.shape[0]) for a in shards)
        el = max(a.expand_len for a in shards)
        n_terms = len(lists)

        def pad1(x, n, fill=0):
            return np.pad(np.asarray(x), (0, n - len(x)), constant_values=fill)

        def pad2(x, n, w, fill=0):
            x = np.asarray(x)
            return np.pad(x, ((0, n - x.shape[0]), (0, w - x.shape[1])), constant_values=fill)

        host = {
            "anchors": np.stack([
                pad1(a.anchors, max_nc, fill=2**31 - 1) for a in shards]).astype(np.int32),
            "c_offsets": np.stack([
                pad1(a.c_offsets, n_terms + 1, fill=int(a.c_offsets[-1])) for a in shards]).astype(np.int32),
            "expand": np.stack([
                pad2(a.expand, max_nc, el) for a in shards]).astype(np.int32),
            "expand_valid": np.stack([
                pad2(a.expand_valid, max_nc, el) for a in shards]).astype(bool),
            "lengths": np.stack([
                pad1(a.lengths, n_terms) for a in shards]).astype(np.int32),
            "doc_base": np.asarray(bounds[:-1], np.int32),
        }
        if mesh is None:
            arrays = {k: jnp.asarray(v) for k, v in host.items()}
        else:
            arrays = {k: jax.device_put(v, NamedSharding(
                mesh, P(shard_axis, *([None] * (v.ndim - 1)))))
                for k, v in host.items()}
        return cls(arrays=arrays, doc_bounds=bounds, n_shards=n_shards, expand_len=el)

    @classmethod
    def from_index(cls, index, n_shards: int, **kw) -> "PartitionedAnchoredIndex":
        """Shard a built index whatever backend it uses: posting lists are
        pulled through the ``SearchBackend`` protocol (``get_list``), so the
        sharded layout works for inverted stores and self-index adapters
        alike.  Positional indexes (``n_tokens`` universe) are cut at
        document boundaries so phrases never span shards."""
        store = index.store
        lists = [np.asarray(store.get_list(i)) for i in range(store.n_lists)]
        universe = int(index.universe_size)
        bounds = None
        if hasattr(index, "n_tokens"):  # positional: align shard cuts to docs
            starts = np.asarray(index.doc_starts, dtype=np.int64)
            picks = np.linspace(0, len(starts), n_shards + 1).astype(np.int64)[1:-1]
            bounds = np.concatenate([[0], starts[picks], [universe]])
        return cls.build(lists, n_docs=universe, n_shards=n_shards, bounds=bounds, **kw)


def _local_serve(local: dict, query_terms: jax.Array, query_lens: jax.Array,
                 max_terms: int, mode: str = "and",
                 row_start: jax.Array | int = 0):
    """Shard-local batched queries (same probe loop as engine.make_serve_step,
    candidates re-based to the shard's id space)."""
    idx = AnchoredIndex(
        anchors=local["anchors"], c_offsets=local["c_offsets"],
        expand=local["expand"], expand_valid=local["expand_valid"],
        lengths=local["lengths"], expand_len=local["expand"].shape[-1])
    cand_vals, cand_valid = candidates_for(idx, query_terms[:, 0], row_start)
    match = _probe_terms(idx, query_terms, query_lens, cand_vals, cand_valid,
                         max_terms, phrase=(mode == "phrase"))
    # back to global ids
    return cand_vals - 1 + local["doc_base"][0], match


def make_partitioned_serve_step(max_terms: int, mesh, shard_axis: str = "data",
                                mode: str = "and"):
    """Returns serve(arrays, query_terms, query_lens, row_start=0) ->
    (vals, mask), each (n_shards, B, C); every probe is shard-local under
    shard_map.  ``mode`` selects AND or offset-shifted phrase probes."""

    in_specs = (
        {k: P(shard_axis, *([None] * (v - 1))) for k, v in
         {"anchors": 2, "c_offsets": 2, "expand": 3, "expand_valid": 3,
          "lengths": 2, "doc_base": 1}.items()},
        P(),  # queries replicated
        P(),
        P(),  # window cursor replicated
    )
    out_specs = (P(shard_axis, None, None), P(shard_axis, None, None))

    def local_fn(arrays, qt, ql, row_start):
        local = {k: v[0] for k, v in arrays.items() if k != "doc_base"}
        local["doc_base"] = arrays["doc_base"]
        vals, mask = _local_serve(local, qt, ql, max_terms, mode=mode,
                                  row_start=row_start)
        return vals[None], mask[None]

    mapped = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)

    def serve(arrays, qt, ql, row_start=0):
        return mapped(arrays, qt, ql, jnp.asarray(row_start, jnp.int32))

    return serve


def serve_partitioned_windowed(pidx: PartitionedAnchoredIndex, serve, qt, ql) -> list[np.ndarray]:
    """Sweep candidate windows across all shards and merge: exact results
    for per-shard lists of any length (concatenating per-shard hits)."""
    c_off = np.asarray(pidx.arrays["c_offsets"])  # (S, n_terms + 1)
    first = np.asarray(qt)[:, 0]
    rows = (c_off[:, first + 1] - c_off[:, first]).max()
    hits: list[list[np.ndarray]] = [[] for _ in range(len(first))]
    for w in range(max(1, -(-int(rows) // MAX_CAND_ROWS))):
        vals, mask = serve(pidx.arrays, qt, ql, w * MAX_CAND_ROWS)
        vals, mask = np.asarray(vals), np.asarray(mask)
        for qi in range(vals.shape[1]):
            hits[qi].append(vals[:, qi][mask[:, qi]])
    return [np.unique(np.concatenate(h)) for h in hits]


def merge_results(vals: np.ndarray, mask: np.ndarray) -> list[np.ndarray]:
    """(S, B, C) -> per-query sorted global doc ids."""
    s, b, c = vals.shape
    out = []
    for qi in range(b):
        hits = vals[:, qi][mask[:, qi]]
        out.append(np.unique(hits))
    return out


# ----------------------------------------------------------------------
# Session-compatible driver over the sharded layout
# ----------------------------------------------------------------------
@dataclass
class PartitionedServer:
    """Batched-server protocol over a :class:`PartitionedAnchoredIndex`.

    With a ``mesh`` the per-window step runs under ``shard_map`` (every
    probe shard-local, queries replicated); without one it loops shards on
    the host through one jitted shard-local step — the single-device path,
    exact and trace-stable, so a ``Session`` can serve a sharded layout on
    any device count.  Only conjunctive and phrase steps exist shard-local
    (``kinds``); the plan compiler routes top-k / doc listing to the host.
    """

    pidx: PartitionedAnchoredIndex
    host_index: object  # the built index the shards were cut from (lookup())
    mesh: object | None = None
    shard_axis: str = "data"
    kinds: frozenset = frozenset({"and", "phrase"})
    _steps: dict = field(default_factory=dict)
    trace_events: int = 0
    _lengths_np: np.ndarray | None = None  # global lengths: sum over shards
    _c_offsets_np: np.ndarray | None = None  # (S, T+1) per-shard C-offsets
    _lane_cum_np: np.ndarray | None = None  # (S, n_c+1) decoded postings

    def __post_init__(self):
        if self._lengths_np is None:
            self._lengths_np = np.asarray(self.pidx.arrays["lengths"]).sum(axis=0)
        if self._c_offsets_np is None:
            self._c_offsets_np = np.asarray(self.pidx.arrays["c_offsets"])
        if self._lane_cum_np is None:
            self._lane_cum_np = lane_cumsum(self.pidx.arrays)

    @classmethod
    def from_index(cls, index, n_shards: int, mesh=None,
                   shard_axis: str = "data", **kw) -> "PartitionedServer":
        """Shard an already-built index (any registered backend) into the
        partitioned layout — the in-memory counterpart of :meth:`open`,
        used by the replicated serving tier to stamp out shard sets."""
        pidx = PartitionedAnchoredIndex.from_index(
            index, n_shards=n_shards, mesh=mesh, shard_axis=shard_axis, **kw)
        return cls(pidx=pidx, host_index=index, mesh=mesh, shard_axis=shard_axis)

    @classmethod
    def open(cls, path, n_shards: int, mesh=None, shard_axis: str = "data",
             **kw) -> "PartitionedServer":
        """Open a persisted index artifact (``repro.core.artifact``) and
        shard it: each shard re-anchors its document range of the reopened
        backend's postings, so a persisted single-machine artifact serves
        a sharded layout without rebuilding the index."""
        from ..core.artifact import open_index

        return cls.from_index(open_index(path), n_shards=n_shards, mesh=mesh,
                              shard_axis=shard_axis, **kw)

    @property
    def trace_count(self) -> int:
        return self.trace_events

    def c_entries(self, list_id: int) -> int:
        """Max C-entries of one list over the shards (window-sweep length)."""
        c = self._c_offsets_np
        return int((c[:, list_id + 1] - c[:, list_id]).max())

    def encode(self, queries: list[list[str]], sort_by_length: bool = False,
               width: int | None = None):
        """Pad to (B, width) global term ids (the shared
        :func:`~repro.serving.engine.encode_queries` step; lengths for the
        rarest-first sort are the shard-summed global list lengths)."""
        with tracing.span("engine.encode"):
            return encode_queries(self.host_index, self._lengths_np, queries,
                                  sort_by_length=sort_by_length, width=width)

    def _step(self, mode: str, width: int):
        key = (mode, width)
        if key not in self._steps:
            if self.mesh is not None:
                raw = make_partitioned_serve_step(
                    max_terms=width, mesh=self.mesh,
                    shard_axis=self.shard_axis, mode=mode)

                def counted(arrays, qt, ql, row_start, _raw=raw):
                    # runs only while jax traces — counts actual retraces
                    self.trace_events += 1
                    return _raw(arrays, qt, ql, row_start)

                counted.__name__ = counted.__qualname__ = f"serve_{mode}_sharded"
                serve = jax.jit(counted)
            else:
                def local(local_arrays, qt, ql, row_start, _mode=mode, _w=width):
                    # runs only while jax traces — counts actual retraces
                    self.trace_events += 1
                    return _local_serve(local_arrays, qt, ql, _w, mode=_mode,
                                        row_start=row_start)

                local.__name__ = local.__qualname__ = f"serve_{mode}_shard"
                jitted = jax.jit(local)

                def serve(arrays, qt, ql, row_start, _j=jitted):
                    outs = []
                    for s in range(self.pidx.n_shards):
                        local_arrays = {k: v[s] for k, v in arrays.items()
                                        if k != "doc_base"}
                        local_arrays["doc_base"] = arrays["doc_base"][s:s + 1]
                        outs.append(_j(local_arrays, qt, ql, row_start))
                    vals = jnp.stack([v for v, _ in outs])
                    mask = jnp.stack([m for _, m in outs])
                    return vals, mask

                serve.__name__ = local.__name__
            self._steps[key] = serve
        return self._steps[key]

    def _sweep(self, mode: str, queries: list[list[str]],
               width: int | None = None) -> list[np.ndarray]:
        qt, ql, ok = self.encode(queries, sort_by_length=(mode != "phrase"),
                                 width=width)
        serve = self._step(mode, qt.shape[1])
        c = self._c_offsets_np
        first = qt[:, 0][ok] if ok.any() else qt[:1, 0]
        rows = int((c[:, first + 1] - c[:, first]).max())
        per_row = int(self.pidx.arrays["expand"].shape[-1])
        hits: list[list[np.ndarray]] = [[] for _ in queries]
        for w in range(max(1, -(-rows // MAX_CAND_ROWS))):
            lanes = (self.pidx.n_shards * qt.shape[0] * MAX_CAND_ROWS * per_row,
                     window_live_lanes(c, self._lane_cum_np, qt[:, 0][ok],
                                       w * MAX_CAND_ROWS))
            with step_window(self, serve, self.pidx.arrays, qt, ql, w,
                             (w * MAX_CAND_ROWS,), lanes) as (vals, mask):
                for qi in range(len(queries)):
                    if ok[qi]:
                        hits[qi].append(vals[:, qi][mask[:, qi]])
        return _merge(hits, ok)

    def conjunctive(self, queries: list[list[str]],
                    width: int | None = None) -> list[np.ndarray]:
        """Batched AND across all shards: sorted global doc ids, exact."""
        return self._sweep("and", queries, width=width)

    def phrase(self, queries: list[list[str]],
               width: int | None = None) -> list[np.ndarray]:
        """Batched phrase across all shards (cut shard bounds at document
        starts so phrases never span shards)."""
        return self._sweep("phrase", queries, width=width)
