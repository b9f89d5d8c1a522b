"""TPU-native anchored representation of Re-Pair compressed lists
(DESIGN.md §2 — the beyond-paper adaptation).

The paper's skipping intersection walks C sequentially, accumulating phrase
sums.  On a vector machine the same information is precomputed once:

    anchor[j] = cumulative d-gap BEFORE C entry j   (prefix sum of phrase sums)

Membership of x in a list becomes: binary-search the list's anchor slice for
x (vectorized over query batches), then verify inside at most ONE phrase via
a bounded expansion table (depth is O(log n), paper §4.4).  Work per probe is
O(log n' + expand), identical to the paper's sampled bound (Cor. 1), but
with no branches and full query-batch parallelism.

``AnchoredIndex`` is the device-resident form consumed by
``repro.serving.engine`` and the ``uihrdc`` dry-run config.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .repair import RePairStore


@contextmanager
def _local_expansion_cache(store: RePairStore):
    """Memoized symbol expansion for the duration of a build, without
    mutating the caller's store: the cache lives in a build-local dict and
    the store's prior ``memoize``/``_memo`` state is restored on exit.
    (If the caller already opted into memoization, their cache keeps
    accumulating as usual.)"""
    prev_memoize = store.memoize
    prev_memo = store._memo
    store.memoize = True
    if not prev_memoize:
        store._memo = {}
    try:
        yield
    finally:
        store.memoize = prev_memoize
        store._memo = prev_memo


@dataclass
class AnchoredIndex:
    """Flat device arrays for batched query execution."""

    anchors: jax.Array  # (n_c,) int32 — cumulative gap before each C entry
    c_offsets: jax.Array  # (n_lists+1,) int32 — list slices into anchors/expand
    expand: jax.Array  # (n_c, expand_len) int32 — per-entry absolute values
    # (bounded expansion; entries longer than expand_len spill, see mask)
    expand_valid: jax.Array  # (n_c, expand_len) bool
    lengths: jax.Array  # (n_lists,) int32
    expand_len: int

    @classmethod
    def from_store(cls, store: RePairStore, expand_len: int = 32) -> "AnchoredIndex":
        n_lists = store.n_lists
        # widen the table to the longest phrase so probes are exact
        max_len = 1
        for s in np.unique(store.c):
            max_len = max(max_len, store.symbol_len(int(s)))
        if max_len > expand_len:
            expand_len = int(2 ** np.ceil(np.log2(max_len)))
        anchors_np = []
        expand_np = []
        valid_np = []
        offsets = store.c_offsets.astype(np.int64)
        with _local_expansion_cache(store):
            for i in range(n_lists):
                lo, hi = int(offsets[i]), int(offsets[i + 1])
                run = 0
                for j in range(lo, hi):
                    sym = int(store.c[j])
                    anchors_np.append(run)
                    gaps = store.expand_symbol(sym)
                    acc = np.cumsum(gaps) + run
                    row = np.zeros(expand_len, dtype=np.int64)
                    vrow = np.zeros(expand_len, dtype=bool)
                    row[: len(acc)] = acc
                    vrow[: len(acc)] = True
                    expand_np.append(row)
                    valid_np.append(vrow)
                    run += int(store.symbol_sum(sym))
        return cls(
            anchors=jnp.asarray(anchors_np, jnp.int32),
            c_offsets=jnp.asarray(np.asarray(offsets), jnp.int32),
            expand=jnp.asarray(np.asarray(expand_np), jnp.int32),
            expand_valid=jnp.asarray(np.asarray(valid_np)),
            lengths=jnp.asarray(np.asarray(store.lengths), jnp.int32),
            expand_len=expand_len,
        )

    def device_bytes(self) -> int:
        tot = 0
        for a in (self.anchors, self.c_offsets, self.expand, self.expand_valid, self.lengths):
            tot += a.size * a.dtype.itemsize
        return tot


def build_anchored(lists: list[np.ndarray], expand_len: int = 32, **kw) -> AnchoredIndex:
    """Re-Pair compress, then anchor (expand table widened to the longest
    phrase so probes are exact)."""
    store = RePairStore.build(lists, variant="skip", **kw)
    return AnchoredIndex.from_store(store, expand_len=expand_len)


@dataclass
class CompressedAnchoredIndex:
    """Compressed device form: anchors plus a shared d-gap *pool*.

    Instead of a dense ``(n_c, expand_len)`` expand table (one padded row
    per C entry, widened to the longest phrase in the whole collection),
    each distinct Re-Pair symbol stores its leaf d-gaps ONCE in ``pool``
    and every C entry holds a ``(ptr, len)`` pointer into it.  On
    repetitive collections the same rules recur across lists, so the pool
    stays near the grammar size while the dense table grows with n_c —
    this is the paper's compression premise carried through to HBM.

    The pool rows are stored *prefix-summed*: the within-symbol scan runs
    once per distinct rule at build time, amortized across every
    occurrence, so the in-sweep decode (``kernels/fused_decode``) is one
    contiguous gather plus an anchor re-base — element ``l`` of entry
    ``j`` is ``anchors[j] + pool[c_ptr[j] + l]``, identical in
    cumulative-gap space to the dense expand rows, so serve results are
    byte-identical to the dense layout.
    """

    anchors: jax.Array  # (n_c,) int32 — cumulative gap before each C entry
    c_offsets: jax.Array  # (n_lists+1,) int32 — list slices into anchors
    c_ptr: jax.Array  # (n_c,) int32 — entry's d-gap slice start in pool
    c_len: jax.Array  # (n_c,) int32 — entry's d-gap count
    pool: jax.Array  # (pool_size,) int32 — per-symbol leaf d-gap prefix sums, deduped
    lengths: jax.Array  # (n_lists,) int32
    max_phrase: int  # longest rule expansion (static decode bound)

    @classmethod
    def from_store(cls, store: RePairStore) -> "CompressedAnchoredIndex":
        n_lists = store.n_lists
        offsets = store.c_offsets.astype(np.int64)
        sym_ptr: dict[int, tuple[int, int]] = {}  # symbol -> (ptr, len) in pool
        pool_parts: list[np.ndarray] = []
        pool_size = 0
        anchors_np: list[int] = []
        ptr_np: list[int] = []
        len_np: list[int] = []
        max_phrase = 1
        with _local_expansion_cache(store):
            for i in range(n_lists):
                lo, hi = int(offsets[i]), int(offsets[i + 1])
                run = 0
                for j in range(lo, hi):
                    sym = int(store.c[j])
                    if sym not in sym_ptr:
                        # prefix-sum once per distinct rule; every
                        # occurrence then decodes with a gather + add
                        psum = np.cumsum(
                            np.asarray(store.expand_symbol(sym), dtype=np.int64))
                        sym_ptr[sym] = (pool_size, len(psum))
                        pool_parts.append(psum)
                        pool_size += len(psum)
                    ptr, ln = sym_ptr[sym]
                    anchors_np.append(run)
                    ptr_np.append(ptr)
                    len_np.append(ln)
                    max_phrase = max(max_phrase, ln)
                    run += int(store.symbol_sum(sym))
        # one decode window of zero padding: row reads become contiguous
        # dynamic slices (ptr, ptr + max_phrase) that never clamp
        pool_parts.append(np.zeros(max_phrase, dtype=np.int64))
        pool = np.concatenate(pool_parts)
        return cls(
            anchors=jnp.asarray(np.asarray(anchors_np, dtype=np.int64), jnp.int32),
            c_offsets=jnp.asarray(np.asarray(offsets), jnp.int32),
            c_ptr=jnp.asarray(np.asarray(ptr_np, dtype=np.int64), jnp.int32),
            c_len=jnp.asarray(np.asarray(len_np, dtype=np.int64), jnp.int32),
            pool=jnp.asarray(pool, jnp.int32),
            lengths=jnp.asarray(np.asarray(store.lengths), jnp.int32),
            max_phrase=int(max_phrase),
        )

    def device_bytes(self) -> int:
        tot = 0
        for a in (self.anchors, self.c_offsets, self.c_ptr, self.c_len, self.pool, self.lengths):
            tot += a.size * a.dtype.itemsize
        return tot


def build_compressed_anchored(lists: list[np.ndarray], **kw) -> CompressedAnchoredIndex:
    """Re-Pair compress, then anchor without expanding: the fused-layout
    counterpart of :func:`build_anchored`."""
    store = RePairStore.build(lists, variant="skip", **kw)
    return CompressedAnchoredIndex.from_store(store)


# ----------------------------------------------------------------------
# batched membership / intersection (jit-able)
# ----------------------------------------------------------------------
def member_batch(idx: AnchoredIndex, list_ids: jax.Array, values: jax.Array) -> jax.Array:
    """For each (list_id, value) pair: is value in that list?  Fully batched.

    values are absolute postings; comparison in cumulative-gap space (+1).
    Anchors are per-list cumulative sums, so the binary search runs within
    the list's [lo, hi) slice — a fixed-depth ``fori_loop`` (vectorizes under
    vmap; the Pallas ``anchor_intersect`` kernel is the tiled-compare TPU
    variant of the same probe).
    """
    targets = values.astype(jnp.int32) + 1
    lo = idx.c_offsets[list_ids]
    hi = idx.c_offsets[list_ids + 1]

    def one(lid_lo, lid_hi, t):
        # find first entry in [lo, hi) whose anchor >= t, then step back:
        # entry j covers targets in (anchor[j], anchor[j] + phrase_sum]
        def body(_, lh):
            l, h = lh
            mid = (l + h) // 2
            active = l < h  # fixed-depth loop: freeze once converged
            go_right = active & (idx.anchors[mid] < t)
            new_l = jnp.where(go_right, mid + 1, l)
            new_h = jnp.where(active & ~go_right, mid, h)
            return (new_l, new_h)

        with jax.named_scope("anchor_search"):
            l, _ = jax.lax.fori_loop(0, 32, body, (lid_lo, lid_hi))
        j = jnp.maximum(l - 1, lid_lo)
        row = idx.expand[j]
        ok = idx.expand_valid[j] & (row == t)
        return ok.any() & (lid_lo < lid_hi)

    return jax.vmap(one)(lo, hi, targets)


def member_batch_compressed(
    idx: CompressedAnchoredIndex, list_ids: jax.Array, values: jax.Array
) -> jax.Array:
    """Fused-layout membership: binary-search the anchors exactly as
    :func:`member_batch`, then — because the covering entry's pool row is
    prefix-summed, hence strictly increasing — a second fixed-depth binary
    search *inside* the row.  Membership touches ``log2(max_phrase)`` pool
    lanes instead of reading a ``max_phrase``-wide expand row, the decoded
    postings never materialize anywhere."""
    if int(idx.anchors.shape[0]) == 0:
        return jnp.zeros(values.shape, dtype=bool)
    targets = values.astype(jnp.int32) + 1
    lo = idx.c_offsets[list_ids]
    hi = idx.c_offsets[list_ids + 1]
    pool_top = int(idx.pool.shape[0]) - 1
    depth = max(int(idx.max_phrase), 1).bit_length() + 1

    def one(lid_lo, lid_hi, t):
        def body(_, lh):
            l, h = lh
            mid = (l + h) // 2
            active = l < h
            go_right = active & (idx.anchors[mid] < t)
            new_l = jnp.where(go_right, mid + 1, l)
            new_h = jnp.where(active & ~go_right, mid, h)
            return (new_l, new_h)

        with jax.named_scope("anchor_search"):
            l, _ = jax.lax.fori_loop(0, 32, body, (lid_lo, lid_hi))
        j = jnp.maximum(l - 1, lid_lo)
        # membership of t in entry j == membership of t - anchors[j] in its
        # sorted prefix-sum row [c_ptr[j], c_ptr[j] + c_len[j])
        tt = t - idx.anchors[j]
        p_lo = idx.c_ptr[j]
        p_hi = p_lo + idx.c_len[j]

        def body2(_, lh):
            l2, h2 = lh
            mid = (l2 + h2) // 2
            active = l2 < h2
            go_right = active & (idx.pool[mid] < tt)
            new_l = jnp.where(go_right, mid + 1, l2)
            new_h = jnp.where(active & ~go_right, mid, h2)
            return (new_l, new_h)

        with jax.named_scope("pool_search"):
            l2, _ = jax.lax.fori_loop(0, depth, body2, (p_lo, p_hi))
        hit = (l2 < p_hi) & (idx.pool[jnp.minimum(l2, pool_top)] == tt)
        return hit & (lid_lo < lid_hi)

    return jax.vmap(one)(lo, hi, targets)
