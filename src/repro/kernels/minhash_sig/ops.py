"""User-facing op: batched MinHash signatures of any (D, L) shingle tile.

``minhash_signatures`` pads the ragged-by-length shingle rows to the
kernel grid, runs the min-reduction on the accelerator, and maps the
sign-flipped int32 minima back to uint32 hash space.  Three execution
paths share one definition of the arithmetic (wraparound 32-bit
multiply-shift + unsigned min):

* ``backend="kernel"`` — the Pallas grid kernel (interpret mode off-TPU);
* ``backend="jnp"``    — a jitted ``lax.map`` over permutations (the
  default off-TPU: batched on device without per-grid-step interpreter
  overhead);
* ``backend="auto"``   — kernel on TPU, jnp elsewhere.

All three agree bit-for-bit with ``ref.minhash_rows_ref`` (asserted in
``tests/test_similarity.py``, including tile-boundary shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..platform import interpret_mode, padded_lanes
from .kernel import _DEAD, _SIGN, LBLK, RBLK, minhash_rows_2d
from .ref import minhash_rows_ref


@functools.partial(jax.jit, static_argnames=())
def _minhash_jnp(s: jax.Array, lens: jax.Array, ab: jax.Array) -> jax.Array:
    """(D, L) int32 shingles, (D, 1) lens, (P, 2) a/b -> (D, P) flipped
    int32 minima (same space as the kernel output)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    live = lane < lens

    def one_perm(row):
        h = s * row[0] + row[1]
        u = h ^ jnp.int32(_SIGN)
        return jnp.where(live, u, jnp.int32(_DEAD)).min(axis=1)

    return jax.lax.map(one_perm, ab).T


def hash_params(num_perm: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic per-permutation multipliers/offsets: ``a`` odd (a
    bijection mod 2^32), ``b`` arbitrary, both uint32."""
    rng = np.random.default_rng(seed)
    a = (rng.integers(0, 2**32, size=num_perm, dtype=np.uint32) | 1)
    b = rng.integers(0, 2**32, size=num_perm, dtype=np.uint32)
    return a, b


def minhash_signatures(shingles: np.ndarray, lens: np.ndarray,
                       a: np.ndarray, b: np.ndarray,
                       backend: str = "auto") -> np.ndarray:
    """MinHash signature matrix: (D, L) uint32 shingle rows (row d live in
    lanes ``[0, lens[d])``) × (P,) hash params -> (D, P) uint32.

    Empty rows sign as 2^32 - 1 (``ref.EMPTY_SIG``).
    """
    shingles = np.ascontiguousarray(shingles, dtype=np.uint32)
    d, l = shingles.shape
    lens = np.asarray(lens, dtype=np.int64).reshape(d)
    if backend == "ref" or d == 0 or l == 0:
        return minhash_rows_ref(shingles, lens, a, b)
    if backend == "auto":
        backend = "kernel" if jax.default_backend() == "tpu" else "jnp"
    s32 = jnp.asarray(shingles.view(np.int32))
    ln = jnp.asarray(lens, dtype=jnp.int32).reshape(d, 1)
    a32 = np.asarray(a, dtype=np.uint32).view(np.int32)
    b32 = np.asarray(b, dtype=np.uint32).view(np.int32)
    if backend == "jnp":
        ab = jnp.asarray(np.stack([a32, b32], axis=1))
        out = _minhash_jnp(s32, ln, ab)
    elif backend == "kernel":
        dpad, lpad = (-d) % RBLK, padded_lanes(l, LBLK) - l
        s_p = jnp.pad(s32, ((0, dpad), (0, lpad)))
        ln_p = jnp.pad(ln, ((0, dpad), (0, 0)))
        out = minhash_rows_2d(s_p, ln_p, jnp.asarray(a32), jnp.asarray(b32),
                              interpret=interpret_mode())[:d]
    else:
        raise ValueError(f"unknown minhash backend {backend!r}; "
                         f"use 'auto', 'kernel', 'jnp', or 'ref'")
    return np.asarray(out).view(np.uint32) ^ np.uint32(0x80000000)
