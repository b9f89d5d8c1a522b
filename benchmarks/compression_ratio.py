"""Compression-ratio trajectory: space_fraction per representative store.

The paper's space axis (index bits / collection bytes) is measured all
over the figure benchmarks, but never *recorded* — so compression
regressions between PRs were anecdotal.  This benchmark builds one
backend per family over the same repetitive collection at two edit
rates (highly repetitive and loosely repetitive) and reports each
store's ``space_fraction`` plus build time, with a JSON object on the
last stdout line.

    PYTHONPATH=src python benchmarks/compression_ratio.py
    PYTHONPATH=src python benchmarks/compression_ratio.py --stores vbyte rlcsa
"""

from __future__ import annotations

import argparse
import json
import time

from repro.core.index import NonPositionalIndex
from repro.data import generate_collection

EDIT_RATES = (0.02, 0.3)
# one backend per compression family (same picks as the test suite's
# FAMILY_REPS): runs, LZ-hybrid, grammar, self-index, referential
FAMILY_REPS = ("rice_runs", "vbyte_lzend", "repair_skip", "rlcsa", "rlz")


def run(stores: tuple[str, ...] = FAMILY_REPS, seed: int = 0) -> list[dict]:
    rows = []
    for edit_rate in EDIT_RATES:
        col = generate_collection(n_articles=5, versions_per_article=20,
                                  words_per_doc=200, edit_rate=edit_rate,
                                  seed=seed)
        for store in stores:
            t0 = time.perf_counter()
            idx = NonPositionalIndex.build(col.docs, store=store)
            build_s = time.perf_counter() - t0
            frac = idx.space_fraction
            rows.append({"store": store, "edit_rate": edit_rate,
                         "n_docs": col.n_docs,
                         "collection_bytes": idx.collection_bytes,
                         "space_fraction": round(frac, 4),
                         "build_s": round(build_s, 2)})
            print(f"{store:>14} edit_rate={edit_rate:<5} "
                  f"space_fraction {frac:7.4f}   build {build_s:6.2f}s")
    return rows


def main() -> None:
    from repro.core.registry import backend_names

    ap = argparse.ArgumentParser()
    ap.add_argument("--stores", type=str, nargs="+", default=list(FAMILY_REPS),
                    choices=backend_names())
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rows = run(stores=tuple(args.stores), seed=args.seed)
    print(json.dumps({"compression_ratio": rows}))


if __name__ == "__main__":
    main()
