"""Ranked (BM25 ``rank<k>:``) serving throughput: pruned vs exhaustive
host top-k and the dense device path at batch sizes 16/64/256.

Three executions of the *same* ranked traffic, all required to return
byte-identical rankings (asserted per query, not sampled):

* **pruned** — the default host path: MaxScore upper-bound pruning skips
  whole postings lists that cannot reach the current top-k threshold.
  Every row reports the observed **skip fraction** (postings skipped /
  total postings) for that batch — the measurable win of the bounds.
* **exhaustive** — the same session with ``rank_pruning`` disabled, so
  every posting of every query term is scored.  The pruned/exhaustive
  q/s ratio is the end-to-end speedup purchased by the upper bounds.
* **device** — dense scatter-add scoring + ``lax.top_k`` through the
  batched server; warmed traffic must report plan-cache hit rate 1.00
  and zero retraces (rank steps are cached per (width, k) like every
  other kind).

Emits a JSON object (one entry per batch size) on the last stdout
line.

    PYTHONPATH=src python benchmarks/ranked_throughput.py
    PYTHONPATH=src python benchmarks/ranked_throughput.py --store rlcsa --k 5
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.index import NonPositionalIndex
from repro.data import generate_collection
from repro.data.queries import sample_traffic
from repro.serving.session import Session

BATCH_SIZES = (16, 64, 256)


def _rank_counters(session) -> dict:
    return {key: getattr(session, f"rank_{key}")
            for key in ("postings_scored", "postings_skipped",
                        "lists_scored", "lists_skipped")}


def run(store: str = "vbyte", k: int = 10, n_terms: int = 3,
        repeats: int = 3, seed: int = 0) -> list[dict]:
    col = generate_collection(n_articles=10, versions_per_article=25,
                              words_per_doc=200, seed=seed)
    idx = NonPositionalIndex.build(col.docs, store=store)
    pruned = Session.build(idx, device=False)
    exhaustive = Session.build(idx, device=False)
    exhaustive.rank_pruning = False
    device = Session.build(idx)
    rng = np.random.default_rng(seed)
    words = list(idx.vocab.id_to_token[:300])

    rows = []
    for bs in BATCH_SIZES:
        queries = sample_traffic("rank", bs, col.docs, words, rng,
                                 n_terms=n_terms, k=k)
        device.execute(queries)  # compile plans / trace the rank step
        warm = device.metrics()
        before = _rank_counters(pruned)

        t0 = time.perf_counter()
        for _ in range(repeats):
            want = pruned.execute(queries)
        pruned_qps = repeats * bs / (time.perf_counter() - t0)
        delta = {key: _rank_counters(pruned)[key] - before[key]
                 for key in before}
        total = delta["postings_scored"] + delta["postings_skipped"]
        skip_fraction = round(delta["postings_skipped"] / total, 4) \
            if total else 0.0

        t0 = time.perf_counter()
        for _ in range(repeats):
            exh = exhaustive.execute(queries)
        exhaustive_qps = repeats * bs / (time.perf_counter() - t0)

        t0 = time.perf_counter()
        for _ in range(repeats):
            dev = device.execute(queries)
        device_qps = repeats * bs / (time.perf_counter() - t0)
        m = device.metrics()
        d_hits = m["plan_cache_hits"] - warm["plan_cache_hits"]
        d_comp = m["plans_compiled"] - warm["plans_compiled"]
        hit_rate = round(d_hits / (d_hits + d_comp), 4) \
            if d_hits + d_comp else 1.0
        retraces = m["jit_traces"] - warm["jit_traces"]

        for q, a, b, c in zip(queries, want, exh, dev):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                f"(seed={seed}, query={q!r}): pruning changed the ranking"
            assert np.array_equal(np.asarray(a), np.asarray(c)), \
                f"(seed={seed}, query={q!r}): device ranking drifted"

        rows.append({"batch_size": bs, "store": store, "k": k,
                     "n_terms": n_terms,
                     "pruned_qps": round(pruned_qps, 1),
                     "exhaustive_qps": round(exhaustive_qps, 1),
                     "device_qps": round(device_qps, 1),
                     "skip_fraction": skip_fraction,
                     "plan_cache_hit_rate": hit_rate,
                     "jit_retraces": retraces})
        print(f"rank{k} b={bs:<4} pruned {pruned_qps:9.1f} q/s   "
              f"exhaustive {exhaustive_qps:9.1f} q/s   "
              f"device {device_qps:9.1f} q/s   skip {skip_fraction:.2f}   "
              f"plan-cache {hit_rate:.2f}   retraces {retraces}")
    return rows


def main() -> None:
    from repro.core.registry import FAMILY_INVERTED, backend_names

    ap = argparse.ArgumentParser()
    ap.add_argument("--store", type=str, default="vbyte",
                    choices=backend_names(family=FAMILY_INVERTED))
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--n-terms", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rows = run(store=args.store, k=args.k, n_terms=args.n_terms,
               repeats=args.repeats, seed=args.seed)
    print(json.dumps({"ranked_throughput": rows}))


if __name__ == "__main__":
    main()
