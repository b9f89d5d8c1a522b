"""User-facing ops: decode / probe ragged C-entry expansions of any count.

The ragged part — gathering each entry's prefix-summed d-gap slice from
the shared pool — happens on the XLA side (a contiguous gather); these
ops take the rectangular (R, L) prefix-sum tile, pad it to the kernel
grid, run the fused Pallas kernel and trim.  L is the collection's
``max_phrase`` bound, padded to the kernel's lane tiling inside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..platform import padded_lanes
from .kernel import LBLK, RBLK, decode_rows_2d, probe_rows_2d


def _pad2(gaps: jax.Array, base: jax.Array, lens: jax.Array):
    r, l = gaps.shape
    rpad = (-r) % RBLK
    lpad = padded_lanes(l, LBLK) - l
    g = jnp.pad(gaps.astype(jnp.int32), ((0, rpad), (0, lpad)))
    b = jnp.pad(base.astype(jnp.int32), (0, rpad)).reshape(-1, 1)
    n = jnp.pad(lens.astype(jnp.int32), (0, rpad)).reshape(-1, 1)
    return g, b, n, r, l


def decode_rows(gaps: jax.Array, base: jax.Array, lens: jax.Array,
                interpret: bool = False):
    """gaps (R, L) int32 prefix-sum rows, base/lens (R,) int32 ->
    (values, valid).

    values (R, L) int32 in cumulative-gap space (posting + 1), valid
    (R, L) bool — the fused-layout equivalent of the dense
    ``expand``/``expand_valid`` rows.
    """
    r = gaps.shape[0]
    if r == 0:
        shape = (0, gaps.shape[1])
        return jnp.zeros(shape, jnp.int32), jnp.zeros(shape, bool)
    g, b, n, r, l = _pad2(gaps, base, lens)
    vals, valid = decode_rows_2d(g, b, n, interpret=interpret)
    return vals[:r, :l], valid[:r, :l] != 0


def probe_rows(gaps: jax.Array, base: jax.Array, lens: jax.Array,
               targets: jax.Array, interpret: bool = False) -> jax.Array:
    """Fused decode + membership probe: (R,) bool, True where targets[r]
    (cumulative-gap space) occurs in row r's expansion."""
    r = gaps.shape[0]
    if r == 0:
        return jnp.zeros((0,), bool)
    g, b, n, r, _ = _pad2(gaps, base, lens)
    t = jnp.pad(targets.astype(jnp.int32), (0, g.shape[0] - r)).reshape(-1, 1)
    hit = probe_rows_2d(g, b, n, t, interpret=interpret)
    return hit[:r, 0] != 0
