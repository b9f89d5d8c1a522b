"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.anchor_intersect.ops import (
    anchor_probe,
    anchor_probe_sliced,
    member_batch_tpu,
)
from repro.kernels.anchor_intersect.ref import anchor_probe_ref, anchor_probe_sliced_ref
from repro.kernels.cin_interaction.ops import cin_layer
from repro.kernels.cin_interaction.ref import cin_layer_ref
from repro.kernels.dgap_decode.ops import dgap_decode
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention_tpu
from repro.models.flash import flash_attention as flash_xla

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


@pytest.mark.parametrize("nb,bs,v,d", [(2, 2, 10, 8), (16, 39, 1000, 10), (8, 5, 128, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag(nb, bs, v, d, dtype):
    idx = jnp.asarray(rng.integers(0, v, (nb, bs)), jnp.int32)
    tab = jnp.asarray(rng.normal(size=(v, d)), dtype)
    got = embedding_bag(idx, tab, bs, interpret=True)
    ref = embedding_bag_ref(idx.reshape(-1), tab, bs)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert float(jnp.max(jnp.abs(got - ref))) < tol


@pytest.mark.parametrize("b,m,hk,h,d", [(4, 6, 8, 5, 10), (16, 39, 200, 200, 10), (3, 4, 4, 7, 130)])
def test_cin_layer(b, m, hk, h, d):
    x0 = jnp.asarray(rng.normal(size=(b, m, d)), jnp.float32)
    xk = jnp.asarray(rng.normal(size=(b, hk, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(m * hk, h)), jnp.float32)
    got = cin_layer(x0, xk, w, interpret=True)
    ref = cin_layer_ref(x0, xk, w)
    rel = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 1e-4


@pytest.mark.parametrize("b,t,h,kh,hd", [(1, 256, 4, 2, 64), (2, 300, 8, 4, 128), (1, 513, 2, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_xla(b, t, h, kh, hd, dtype):
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, kh, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, kh, hd)), dtype)
    got = flash_attention_tpu(q, k, v, interpret=True)
    ref = flash_xla(q, k, v, True, 128)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))) < tol


def test_flash_xla_gradients_match_naive():
    """Custom VJP vs autodiff-through-naive-attention."""
    b, t, h, kh, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)

    def naive(q, k, v):
        g = h // kh
        kk = jnp.repeat(k, g, axis=2)
        vv = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    f1 = lambda q, k, v: (flash_xla(q, k, v, True, 16) ** 2).sum()
    f2 = lambda q, k, v: (naive(q, k, v) ** 2).sum()
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b_))) < 1e-4


@pytest.mark.parametrize("e,c,d,f", [(2, 8, 16, 16), (4, 100, 64, 200), (3, 256, 512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm(e, c, d, f, dtype):
    from repro.kernels.moe_gemm.ops import moe_gemm
    from repro.kernels.moe_gemm.ref import moe_gemm_ref

    buf = jnp.asarray(rng.normal(size=(e, c, d)), dtype)
    w = jnp.asarray(rng.normal(size=(e, d, f)), dtype)
    got = moe_gemm(buf, w, interpret=True)
    ref = moe_gemm_ref(buf, w)
    rel = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 1e-3


@pytest.mark.parametrize("b,s,h,kh,hd", [(2, 512, 4, 2, 64), (1, 1024, 8, 8, 128), (3, 700, 4, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(b, s, h, kh, hd, dtype):
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.models.layers import decode_attention

    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kh, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kh, hd)), dtype)
    pos = jnp.asarray(rng.integers(0, s, b), jnp.int32)
    got = flash_decode(q, k, v, pos, interpret=True)
    ref = decode_attention(q, k, v, pos)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))) < tol


def test_flash_decode_position_zero():
    """Edge: position 0 attends only to the first cache slot."""
    from repro.kernels.flash_decode.ops import flash_decode

    b, s, h, kh, hd = 1, 512, 2, 1, 32
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.float32)
    got = flash_decode(q, k, v, jnp.zeros(b, jnp.int32), interpret=True)
    # attending to one slot: output == v[0] per head group
    ref = jnp.broadcast_to(v[:, 0:1, 0][:, :, None, :], (b, 1, h, hd))
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-5
