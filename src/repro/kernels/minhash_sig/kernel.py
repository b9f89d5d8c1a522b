"""Pallas TPU kernel: batched MinHash signatures over hashed shingles.

Signature computation is an embarrassingly parallel min-reduction: for
document *d* and permutation *p*, ``sig[d, p] = min over shingles s of
h_p(s)`` with ``h_p(s) = a_p * s + b_p (mod 2^32)`` — a multiply-shift
universal hash evaluated in wraparound int32 arithmetic (no modulus, no
64-bit lanes).  Unsigned ordering on the VPU uses the sign-flip trick:
``u = h ^ 0x8000_0000`` maps uint32 order onto int32 order, so the lane
min over ``u`` is the unsigned min over ``h``.

Grid: (document row block, shingle lane block).  Each step reads a
(RBLK, lb) shingle tile, evaluates all P permutations over it (the hash
parameters sit whole in SMEM) and folds the lane minima into the
(RBLK, P) output block, which stays resident across the minor lane axis.
Dead lanes (``lane >= len``) are forced to INT32_MAX, the unsigned-order
image of 2^32 - 1, which is also the defined signature of an empty
shingle set.

VMEM per step: RBLK * lb int32 (512 KiB at RBLK=64, lb=LBLK=2048) plus
the (RBLK, P) output block — independent of the longest row, so rows of
any length compile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import padded_lanes

RBLK = 64  # document rows per grid step
LBLK = 2048  # shingle lanes per grid step (rows longer than this tile)

_SIGN = -2147483648  # 0x8000_0000 as int32: the unsigned-order flip
_DEAD = 2147483647  # INT32_MAX: unsigned-order image of 2^32 - 1


def _sig_kernel(a_ref, b_ref, s_ref, len_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, _DEAD, jnp.int32)

    s = s_ref[...]  # (RBLK, lb) int32 shingle hashes (garbage beyond len)
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * s.shape[1]
    live = lane < len_ref[...]  # (RBLK, 1) lens broadcast over lanes
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

    def one_perm(p, acc):
        h = s * a_ref[p] + b_ref[p]  # int32 wraparound == mod 2^32
        u = jnp.where(live, h ^ jnp.int32(_SIGN), jnp.int32(_DEAD))
        m = u.min(axis=1, keepdims=True)  # (RBLK, 1)
        return jnp.where(col == p, jnp.minimum(acc, m), acc)

    out_ref[...] = jax.lax.fori_loop(0, out_ref.shape[1], one_perm, out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def minhash_rows_2d(shingles: jax.Array, lens: jax.Array, a: jax.Array,
                    b: jax.Array, interpret: bool = False) -> jax.Array:
    """shingles (D, L) int32, lens (D, 1) int32, a/b (P,) int32;
    D % RBLK == 0, L == padded_lanes(L, LBLK).

    Returns (D, P) int32 signatures in sign-flipped (unsigned-order)
    space; ``ops.minhash_signatures`` maps them back to uint32 values.
    """
    d, l = shingles.shape
    p = a.shape[0]
    lb = min(l, LBLK)
    assert d % RBLK == 0 and l == padded_lanes(l, LBLK)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _sig_kernel,
        grid=(d // RBLK, l // lb),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((RBLK, lb), lambda i, j: (i, j)),
            pl.BlockSpec((RBLK, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((RBLK, p), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, p), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, shingles, lens)
