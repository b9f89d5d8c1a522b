"""Pallas TPU kernel: batched anchor probe (the TPU form of RePair-Skip).

For sorted anchor values A (prefix sums of Re-Pair phrase sums over C) and
a batch of query values Q, computes per query

    idx[q]   = |{ a in A : a <= q }|      (searchsorted, 'right')
    found[q] = any(a == q)

On a CPU this is a binary search; on the VPU a tiled compare-and-reduce
saturates the vector unit with zero branch divergence: grid =
(query_blocks, anchor_blocks), anchor blocks stream through VMEM while the
per-query accumulators live in VMEM scratch across the minor grid axis.

VMEM per step: (QBLK) queries + (ABLK) anchors + (QBLK, ABLK) int32 compare
tile = 8*128*4 + ... well under budget at QBLK=256, ABLK=2048.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QBLK = 256
ABLK = 2048
PAD_VAL = 2**31 - 1  # anchors padded with +inf-like sentinel


def _probe_kernel(q_ref, a_ref, idx_ref, found_ref, acc_idx, acc_found):
    aj = pl.program_id(1)

    @pl.when(aj == 0)
    def _init():
        acc_idx[...] = jnp.zeros_like(acc_idx)
        acc_found[...] = jnp.zeros_like(acc_found)

    q = q_ref[...]  # (QBLK, 1) int32
    a = a_ref[...]  # (1, ABLK) int32
    le = (a <= q).astype(jnp.int32)  # (QBLK, ABLK)
    eq = (a == q).astype(jnp.int32)
    acc_idx[...] += le.sum(axis=1, keepdims=True)
    acc_found[...] = jnp.maximum(acc_found[...], eq.max(axis=1, keepdims=True))

    @pl.when(aj == pl.num_programs(1) - 1)
    def _emit():
        idx_ref[...] = acc_idx[...]
        found_ref[...] = acc_found[...]


def _probe_slice_kernel(q_ref, lo_ref, hi_ref, a_ref, l_ref, acc_l):
    """Per-list-sliced variant: count anchors strictly below q *within the
    query's [lo, hi) slice* of the global anchor array — the batched form of
    the serve step's inner binary search (one probe per (term, candidate))."""
    aj = pl.program_id(1)

    @pl.when(aj == 0)
    def _init():
        acc_l[...] = jnp.zeros_like(acc_l)

    q = q_ref[...]  # (QBLK, 1) int32
    lo = lo_ref[...]  # (QBLK, 1) int32
    hi = hi_ref[...]  # (QBLK, 1) int32
    a = a_ref[...]  # (1, ABLK) int32
    col = jax.lax.broadcasted_iota(jnp.int32, (QBLK, ABLK), 1) + aj * ABLK
    in_slice = (col >= lo) & (col < hi)
    lt = (in_slice & (a < q)).astype(jnp.int32)  # (QBLK, ABLK)
    acc_l[...] += lt.sum(axis=1, keepdims=True)

    @pl.when(aj == pl.num_programs(1) - 1)
    def _emit():
        l_ref[...] = lo_ref[...] + acc_l[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def anchor_probe_sliced_2d(queries: jax.Array, lo: jax.Array, hi: jax.Array,
                           anchors: jax.Array, interpret: bool = False):
    """queries/lo/hi (NQ, 1) int32; anchors (1, NA) int32, padded with
    PAD_VAL.  Returns l (NQ, 1): first position in [lo, hi) whose anchor is
    >= q (== hi when none), the lower-bound step of ``member_batch``."""
    nq = queries.shape[0]
    na = anchors.shape[1]
    assert nq % QBLK == 0 and na % ABLK == 0
    grid = (nq // QBLK, na // ABLK)
    qspec = pl.BlockSpec((QBLK, 1), lambda qi, ai: (qi, 0))
    return pl.pallas_call(
        _probe_slice_kernel,
        name="anchor_probe_sliced",
        grid=grid,
        in_specs=[qspec, qspec, qspec,
                  pl.BlockSpec((1, ABLK), lambda qi, ai: (0, ai))],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((nq, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((QBLK, 1), jnp.int32)],
        interpret=interpret,
    )(queries, lo, hi, anchors)


@functools.partial(jax.jit, static_argnames=("interpret",))
def anchor_probe_2d(queries: jax.Array, anchors: jax.Array, interpret: bool = False):
    """queries (NQ, 1) int32; anchors (1, NA) int32 sorted, padded with PAD_VAL."""
    nq = queries.shape[0]
    na = anchors.shape[1]
    assert nq % QBLK == 0 and na % ABLK == 0
    grid = (nq // QBLK, na // ABLK)
    return pl.pallas_call(
        _probe_kernel,
        name="anchor_probe",
        grid=grid,
        in_specs=[
            pl.BlockSpec((QBLK, 1), lambda qi, ai: (qi, 0)),
            pl.BlockSpec((1, ABLK), lambda qi, ai: (0, ai)),
        ],
        out_specs=[
            pl.BlockSpec((QBLK, 1), lambda qi, ai: (qi, 0)),
            pl.BlockSpec((QBLK, 1), lambda qi, ai: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((QBLK, 1), jnp.int32),
            pltpu.VMEM((QBLK, 1), jnp.int32),
        ],
        interpret=interpret,
    )(queries, anchors)
