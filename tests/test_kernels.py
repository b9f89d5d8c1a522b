"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.anchor_intersect.ops import (
    anchor_probe,
    anchor_probe_sliced,
    member_batch_tpu,
)
from repro.kernels.anchor_intersect.ref import anchor_probe_ref, anchor_probe_sliced_ref
from repro.kernels.dgap_decode.ops import dgap_decode

rng = np.random.default_rng(0)


@pytest.mark.parametrize("n", [1, 511, 65535, 65536, 65537, 131072 + 13])
@pytest.mark.parametrize("hi", [2, 1000, 2**20])
def test_dgap_decode(n, hi):
    # n sweeps the kernel tile boundary (BLOCK_ROWS*LANES = 65536) ± 1
    g = jnp.asarray(rng.integers(1, hi, n), jnp.int32)
    got = dgap_decode(g, interpret=True)
    assert jnp.array_equal(got, jnp.cumsum(g) - 1)


def test_dgap_decode_empty_and_single():
    """Zero-length input used to hit an empty Pallas grid; n <= 1 shortcuts."""
    out = dgap_decode(jnp.zeros((0,), jnp.int32), interpret=True)
    assert out.shape == (0,) and out.dtype == jnp.int32
    assert jnp.array_equal(dgap_decode(jnp.asarray([7], jnp.int32), interpret=True),
                           jnp.asarray([6], jnp.int32))


@pytest.mark.parametrize("r,l", [(0, 8), (1, 1), (3, 41), (255, 127), (256, 128), (257, 129),
                                 (3, 1500), (2, 2100)])
def test_fused_decode_rows(r, l):
    """Fused decode kernel vs the NumPy oracle across the RBLK/LANE tile
    boundaries (256 rows x 128 lanes) ± 1, and rows wider than one lane
    tile (LBLK = 1024)."""
    from repro.kernels.fused_decode.ops import decode_rows
    from repro.kernels.fused_decode.ref import decode_rows_ref

    gaps = rng.integers(1, 50, size=(r, l)).astype(np.int32)
    lens = rng.integers(0, l + 1, size=r).astype(np.int32)
    base = rng.integers(0, 10**6, size=r).astype(np.int32)
    vals, valid = decode_rows(jnp.asarray(gaps), jnp.asarray(base),
                              jnp.asarray(lens), interpret=True)
    rvals, rvalid = decode_rows_ref(gaps, base, lens)
    assert np.array_equal(np.asarray(valid), rvalid)
    assert np.array_equal(np.asarray(vals)[rvalid], rvals[rvalid])


@pytest.mark.parametrize("r,l", [(0, 8), (3, 41), (257, 129), (6, 2100)])
def test_fused_probe_rows(r, l):
    """Fused decode+membership kernel vs the NumPy oracle: hits on real
    row values, misses on values never decoded."""
    from repro.kernels.fused_decode.ops import probe_rows
    from repro.kernels.fused_decode.ref import decode_rows_ref, probe_rows_ref

    gaps = rng.integers(1, 50, size=(r, l)).astype(np.int32)
    lens = rng.integers(1, l + 1, size=r).astype(np.int32)
    base = rng.integers(0, 10**6, size=r).astype(np.int32)
    rvals, _ = decode_rows_ref(gaps, base, lens)
    hit_lane = rng.integers(0, np.maximum(lens, 1))
    targets = np.where(np.arange(r) % 2 == 0,
                       rvals[np.arange(r), hit_lane], -5).astype(np.int32)
    got = probe_rows(jnp.asarray(gaps), jnp.asarray(base), jnp.asarray(lens),
                     jnp.asarray(targets), interpret=True)
    assert np.array_equal(np.asarray(got), probe_rows_ref(gaps, base, lens, targets))


@pytest.mark.parametrize("d,l", [(1, 1), (65, 130), (3, 2049)])
def test_minhash_rows_kernel(d, l):
    """MinHash kernel vs the NumPy oracle across the row block (64), the
    lane boundary (128) and the lane tile (LBLK = 2048), with empty rows."""
    from repro.kernels.minhash_sig.ops import hash_params, minhash_signatures
    from repro.kernels.minhash_sig.ref import minhash_rows_ref

    shingles = rng.integers(0, 2**32, size=(d, l), dtype=np.uint32)
    lens = rng.integers(0, l + 1, size=d)
    lens[0] = 0
    a, b = hash_params(64, seed=3)
    got = minhash_signatures(shingles, lens, a, b, backend="kernel")
    assert np.array_equal(got, minhash_rows_ref(shingles, lens, a, b))


@pytest.mark.parametrize("nq,na", [(1, 1), (7, 100), (300, 5000), (1024, 2048)])
def test_anchor_probe(nq, na):
    anchors = jnp.asarray(np.unique(rng.integers(0, 10**6, na)), jnp.int32)
    half = rng.choice(np.asarray(anchors), nq // 2 + 1)
    queries = jnp.asarray(np.concatenate([rng.integers(0, 10**6, nq // 2), half])[:nq], jnp.int32)
    idx, found = anchor_probe(queries, anchors, interpret=True)
    ridx, rfound = anchor_probe_ref(queries, anchors)
    assert jnp.array_equal(idx, ridx)
    assert jnp.array_equal(found, rfound.astype(jnp.int32))


@pytest.mark.parametrize("nq,na,nl", [(7, 100, 3), (300, 5000, 12), (1024, 2048, 40)])
def test_anchor_probe_sliced(nq, na, nl):
    """Per-list-sliced lower bound (the serve step's batched probe)."""
    # anchors sorted within each list slice, not globally
    bounds = np.sort(np.concatenate([[0, na], rng.integers(0, na, nl - 1)]))
    anchors = np.concatenate([np.sort(rng.integers(0, 10**6, hi - lo))
                              for lo, hi in zip(bounds[:-1], bounds[1:])])
    lists = rng.integers(0, nl, nq)
    lo = bounds[lists].astype(np.int32)
    hi = bounds[lists + 1].astype(np.int32)
    queries = rng.integers(0, 10**6, nq).astype(np.int32)
    got = anchor_probe_sliced(jnp.asarray(queries), jnp.asarray(lo), jnp.asarray(hi),
                              jnp.asarray(anchors, jnp.int32), interpret=True)
    ref = anchor_probe_sliced_ref(queries, lo, hi, anchors)
    assert jnp.array_equal(got, jnp.asarray(ref))


def test_member_batch_tpu_matches_member_batch():
    """The probe='kernel' serving path == the vmapped binary search,
    including empty lists (must never match) and out-of-range values."""
    from repro.core.anchors import build_anchored, member_batch

    lists = []
    for i in range(12):
        if i == 5:
            lists.append(np.asarray([], dtype=np.int64))  # empty list
        else:
            lists.append(np.flatnonzero(
                np.repeat(rng.random(40) < 0.4, 10)).astype(np.int64))
    aidx = build_anchored(lists)
    ids = rng.integers(0, len(lists), 400).astype(np.int32)
    vals = rng.integers(0, 500, 400).astype(np.int32)
    ref = member_batch(aidx, jnp.asarray(ids), jnp.asarray(vals))
    got = member_batch_tpu(aidx.anchors, aidx.c_offsets, aidx.expand,
                           aidx.expand_valid, jnp.asarray(ids), jnp.asarray(vals),
                           interpret=True)
    assert jnp.array_equal(got, ref)
    assert not bool(np.asarray(got)[ids == 5].any())  # empty list never hits
