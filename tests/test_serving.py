"""Anchored index + the batched AND serve step."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anchors import AnchoredIndex, build_anchored, member_batch
from repro.serving.engine import make_serve_step
from repro.serving.plan import AND


@pytest.fixture(scope="module")
def lists(rep_lists=None):
    rng = np.random.default_rng(7)
    out = []
    for _ in range(30):
        present = np.repeat(rng.random(80) < 0.35, 20) ^ (rng.random(1600) < 0.02)
        l = np.flatnonzero(present).astype(np.int64)
        out.append(l if len(l) else np.asarray([1], dtype=np.int64))
    return out


@pytest.fixture(scope="module")
def aidx(lists):
    return build_anchored(lists)


def test_member_batch_exhaustive(lists, aidx):
    xs = np.arange(1700)
    for i in (0, 9, 29):
        got = np.asarray(member_batch(aidx, jnp.full(len(xs), i, jnp.int32),
                                      jnp.asarray(xs, jnp.int32)))
        assert np.array_equal(got, np.isin(xs, lists[i])), i


def test_member_batch_mixed_lists(lists, aidx):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, len(lists), 500).astype(np.int32)
    vals = rng.integers(0, 1700, 500).astype(np.int32)
    got = np.asarray(member_batch(aidx, jnp.asarray(ids), jnp.asarray(vals)))
    ref = np.asarray([int(v) in set(lists[i].tolist()) for i, v in zip(ids, vals)])
    assert np.array_equal(got, ref)


def test_serve_step_and_queries(lists, aidx):
    serve = jax.jit(make_serve_step(max_terms=3, mode=AND))
    arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
              "expand": aidx.expand, "expand_valid": aidx.expand_valid,
              "lengths": aidx.lengths}
    qt = jnp.asarray([[2, 7, 0], [11, 3, 19], [5, 0, 0]], jnp.int32)
    ql = jnp.asarray([2, 3, 1], jnp.int32)
    vals, mask = serve(arrays, qt, ql)
    for qi, terms in enumerate([[2, 7], [11, 3, 19], [5]]):
        ref = lists[terms[0]]
        for t in terms[1:]:
            ref = np.intersect1d(ref, lists[t])
        got = np.unique(np.asarray(vals[qi])[np.asarray(mask[qi])])
        cap = np.asarray(vals[qi]).max()
        assert np.array_equal(got, ref[ref <= cap]), qi


def test_anchor_sizes(aidx):
    assert aidx.device_bytes() > 0
    assert aidx.anchors.shape[0] + 1 >= aidx.c_offsets.shape[0]


def test_partitioned_index_matches_global(lists):
    """Document-partitioned serving == global AND results (manual per-shard
    loop; the shard_map path is exercised in test_distributed)."""
    from repro.serving.partitioned import PartitionedAnchoredIndex, _local_serve, merge_results

    n_docs = 1600
    pidx = PartitionedAnchoredIndex.build(lists, n_docs=n_docs, n_shards=4)
    qt = jnp.asarray([[2, 7], [11, 3], [5, 5]], jnp.int32)
    ql = jnp.asarray([2, 2, 1], jnp.int32)
    all_vals, all_mask = [], []
    for s in range(4):
        local = {k: np.asarray(v[s]) for k, v in pidx.arrays.items() if k != "doc_base"}
        local = {k: jnp.asarray(v) for k, v in local.items()}
        local["doc_base"] = pidx.arrays["doc_base"][s : s + 1]
        vals, mask = _local_serve(local, qt, ql, max_terms=2)
        all_vals.append(np.asarray(vals))
        all_mask.append(np.asarray(mask))
    vals = np.stack(all_vals)
    mask = np.stack(all_mask)
    merged = merge_results(vals, mask)
    for qi, terms in enumerate([[2, 7], [11, 3], [5]]):
        ref = lists[terms[0]]
        for t in terms[1:]:
            ref = np.intersect1d(ref, lists[t])
        # per-shard candidate caps: compare within each shard's cap
        got = merged[qi]
        ok = np.isin(got, ref).all()
        assert ok, (qi, got[:10], ref[:10])
        # no hit lost below the per-shard caps
        for s in range(4):
            lo, hi = pidx.doc_bounds[s], pidx.doc_bounds[s + 1]
            cap = vals[s, qi].max()
            expect = ref[(ref >= lo) & (ref < hi) & (ref <= cap)]
            shard_got = np.unique(vals[s, qi][mask[s, qi]])
            assert np.array_equal(shard_got, expect), (qi, s)


def test_engine_imports_nothing_from_the_session():
    """The device layer sits below the session: importing it in a fresh
    interpreter loads no ``repro.serving.session``."""
    root = Path(__file__).resolve().parents[1]
    code = ("import sys, repro.serving.engine; "
            "print('repro.serving.session' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=root, timeout=120,
                          env={**os.environ, "PYTHONPATH": str(root / "src"),
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"
