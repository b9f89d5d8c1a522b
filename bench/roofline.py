"""Least bytes a step must move for the queries it answered, from the
host index and the queries, never from the server's padded shapes, so a
share reads the same work whatever implements the step.  The peaks it is
set against live in ``peaks.json``, keyed by ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

INT32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def list_bytes(store, term_id: int) -> int:
    """One posting list in its compressed Re-Pair form, read once: each C
    entry's anchor, pool pointer and length (int32 each) and the pool
    lanes the entries cover, one int32 per posting."""
    c = np.asarray(store.c_offsets)
    n_c = int(c[term_id + 1] - c[term_id])
    return 3 * INT32 * n_c + INT32 * int(store.lengths[term_id])


def sweep_bytes(index, queries: list[list[str]], answer_lens: list[int]) -> int:
    """The sweep step, AND or phrase: every query term's list read once,
    every answer written once (int32 per hit).  ``index`` is the host index
    that served the queries, whose ``lookup`` maps a query term to its
    list; ``queries`` holds each query's terms as parsed for its kind."""
    total = INT32 * int(sum(answer_lens))
    for terms in queries:
        for t in terms:
            tid = index.lookup(t)
            if tid is not None:
                total += list_bytes(index.store, tid)
    return total
