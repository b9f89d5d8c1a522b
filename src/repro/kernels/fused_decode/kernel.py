"""Pallas TPU kernel: fused bounded rule expansion for the compressed
device layout (``CompressedAnchoredIndex``).

Each grid row is one Re-Pair C entry: its leaf d-gap *prefix sums*
(gathered from the shared pool on the XLA side — the gather is ragged,
the decode is not), its anchor (cumulative gap before the entry) and its
gap count.  The within-symbol scan that ``dgap_decode`` performs per
stream runs once per distinct rule at build time instead — amortized
across every occurrence of the rule — so the kernel reconstructs
absolute cumulative-gap values with a per-row anchor re-base + lane mask
(rows are independent C entries, so no SMEM carry is needed) and either

  * emits the decoded rows + validity mask (``_decode_kernel``), the
    drop-in replacement for reading dense ``expand``/``expand_valid``
    rows, or
  * fuses the shifted membership compare-and-reduce on top
    (``_probe_kernel``), so probe targets never round-trip decoded
    postings through HBM at all.

VMEM per step: a (RBLK, lb) int32 tile, lb = min(L, LBLK) with L =
max_phrase padded to the lane tiling (``platform.padded_lanes``) — 1 MiB at
RBLK=256, lb=LBLK=1024.  The lane axis is a grid axis, so the tile does
not grow with ``max_phrase``, which reaches the document count in the
non-positional index (a word in every document is one rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import padded_lanes

RBLK = 256  # rows (C entries) per grid step
LBLK = 1024  # lanes per grid step (rows wider than this tile)


def _row_values(g_ref, base_ref, len_ref):
    """(RBLK, lb) anchor re-base of the prefix-summed rows + lane mask."""
    g = g_ref[...]  # (RBLK, lb) int32 prefix sums (garbage beyond len)
    ln = len_ref[...]  # (RBLK, 1) int32
    lane = (jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
            + pl.program_id(1) * g.shape[1])
    live = lane < ln
    return base_ref[...] + g, live


def _decode_kernel(g_ref, base_ref, len_ref, out_ref, valid_ref):
    vals, live = _row_values(g_ref, base_ref, len_ref)
    out_ref[...] = vals
    valid_ref[...] = live.astype(jnp.int32)


def _probe_kernel(g_ref, base_ref, len_ref, t_ref, hit_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hit_ref[...] = jnp.zeros_like(hit_ref)

    vals, live = _row_values(g_ref, base_ref, len_ref)
    hit = live & (vals == t_ref[...])  # t broadcast (RBLK, 1) -> (RBLK, lb)
    hit_ref[...] = jnp.maximum(
        hit_ref[...], hit.any(axis=1, keepdims=True).astype(jnp.int32))


def _specs(r: int, l: int):
    """Grid + (row-column, gap-tile) block specs over (R, L) rows."""
    lb = min(l, LBLK)
    assert r % RBLK == 0 and l == padded_lanes(l, LBLK)
    rowspec = pl.BlockSpec((RBLK, 1), lambda i, j: (i, 0))
    gspec = pl.BlockSpec((RBLK, lb), lambda i, j: (i, j))
    return (r // RBLK, l // lb), rowspec, gspec


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_rows_2d(gaps: jax.Array, base: jax.Array, lens: jax.Array,
                   interpret: bool = False):
    """gaps (R, L) int32 prefix-sum rows, base/lens (R, 1) int32;
    R % RBLK == 0, L == padded_lanes(L, LBLK).

    Returns (values, valid_i32), both (R, L) int32: values in
    cumulative-gap space (posting + 1), valid nonzero where lane < len.
    """
    r, l = gaps.shape
    grid, rowspec, gspec = _specs(r, l)
    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[gspec, rowspec, rowspec],
        out_specs=[gspec, gspec],
        out_shape=[
            jax.ShapeDtypeStruct((r, l), jnp.int32),
            jax.ShapeDtypeStruct((r, l), jnp.int32),
        ],
        interpret=interpret,
    )(gaps, base, lens)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_rows_2d(gaps: jax.Array, base: jax.Array, lens: jax.Array,
                  targets: jax.Array, interpret: bool = False):
    """Fused decode + membership: does target[r] occur in row r's expansion?

    Shapes as :func:`decode_rows_2d` plus targets (R, 1) int32 in
    cumulative-gap space.  Returns (R, 1) int32 (nonzero = hit).
    """
    r, l = gaps.shape
    grid, rowspec, gspec = _specs(r, l)
    return pl.pallas_call(
        _probe_kernel,
        grid=grid,
        in_specs=[gspec, rowspec, rowspec, rowspec],
        out_specs=rowspec,
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(gaps, base, lens, targets)
