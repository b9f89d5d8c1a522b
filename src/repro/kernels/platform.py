"""Where the Pallas kernels run (compiled on the TPU, interpreted on the
CPU), and the TPU lane tiling their row kernels share."""

from __future__ import annotations

import jax

LANE = 128  # TPU lane width: a block's minor dimension is a multiple of it


def interpret_mode() -> bool:
    """``interpret=`` for the kernels on the default backend.

    The TPU compiles them (Mosaic); the CPU backend interprets them, which
    is how the tests exercise them.  Any other backend is an error rather
    than a silent fallback to the interpreter."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"the Pallas kernels target the TPU (or the CPU "
                           f"interpreter); the default backend is {backend!r}")
    return backend == "cpu"


def padded_lanes(l: int, tile: int) -> int:
    """Row width a grid of ``tile``-lane blocks accepts for rows of ``l``
    lanes: the 128-lane boundary, then a whole number of tiles, so the
    block (and its VMEM) stays ``tile`` wide however long the rows get."""
    l = max(LANE, -(-l // LANE) * LANE)
    return l if l <= tile else -(-l // tile) * tile
