"""Open-loop serving latency: tail percentiles under Poisson arrivals.

``serving_throughput.py`` measures the closed-loop steady state (back to
back pre-assembled batches); a production front is judged open-loop —
queries arrive on their own clock, queue, and are coalesced into
micro-batches by the frontend.  This benchmark drives the
:class:`~repro.serving.frontend.MicroBatchFrontend` at **three offered
loads** (fractions of the measured closed-loop capacity, default
0.5x / 1x / 2x — the 2x point exercises admission control) and reports per
load: p50/p95/p99/mean latency, achieved q/s, **reject rate** (typed
queue-full rejections, never hangs), and **result-cache hit rate** (the
traffic is drawn from a finite query pool, like real serving traffic).

Emits a JSON object on stdout after the human-readable table.

    PYTHONPATH=src python benchmarks/serving_latency.py
    PYTHONPATH=src python benchmarks/serving_latency.py --store vbyte --queries 150
    PYTHONPATH=src python benchmarks/serving_latency.py --loads 0.25,1,4
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.queries import sample_traffic
from repro.serving.frontend import FrontendConfig, run_open_loop
from repro.serving.session import Session


def run(store: str = "repair_skip", probe: str = "vmap", queries: int = 200,
        loads: tuple[float, ...] = (0.5, 1.0, 2.0), pool: int = 48,
        max_batch: int = 32, max_delay_ms: float = 2.0,
        max_pending: int = 64, seed: int = 0) -> dict:
    col = generate_collection(n_articles=8, versions_per_article=16,
                              words_per_doc=150, seed=seed)
    idx = NonPositionalIndex.build(col.docs, store=store)
    pidx = PositionalIndex.build(col.docs, store=store)
    session = Session.build(idx, positional=pidx, probe=probe)
    rng = np.random.default_rng(seed)
    words = [w for w in idx.vocab.id_to_token[:300]]
    # a finite query pool (mixed kinds) sampled with repetition: repeated
    # traffic is what gives the result cache something to absorb
    query_pool = sample_traffic("mixed", pool, col.docs, words, rng)
    traffic = [query_pool[int(rng.integers(pool))] for _ in range(queries)]

    # closed-loop capacity: the offered loads are fractions of this
    session.execute(query_pool)  # compile plans / trace device steps
    t0 = time.perf_counter()
    session.execute(traffic)
    capacity = len(traffic) / (time.perf_counter() - t0)

    cfg = FrontendConfig(max_batch=max_batch, max_delay=max_delay_ms / 1e3,
                         max_pending=max_pending)
    rows = []
    for load in loads:
        rate = load * capacity
        # fresh frontend per load: each row is one cold cache + scheduler
        _, rep = run_open_loop(session, traffic, rate_qps=rate, config=cfg,
                               seed=seed + int(load * 1000))
        lat = rep["latency"]
        rows.append({"load": load, "offered_qps": rep["offered_qps"],
                     "achieved_qps": rep["achieved_qps"],
                     "p50_ms": lat.get("p50_ms"), "p95_ms": lat.get("p95_ms"),
                     "p99_ms": lat.get("p99_ms"), "mean_ms": lat.get("mean_ms"),
                     "queue_depth_max": lat.get("queue_depth_max", 0),
                     "reject_rate": rep["reject_rate"],
                     "cache_hit_rate": rep["cache_hit_rate"],
                     "mean_batch": rep["mean_batch"]})
        print(f"load {load:>4}x  offered {rep['offered_qps']:8.1f} q/s  "
              f"achieved {rep['achieved_qps']:8.1f} q/s  "
              f"p50 {lat.get('p50_ms', 0):8.2f}ms  "
              f"p95 {lat.get('p95_ms', 0):8.2f}ms  "
              f"p99 {lat.get('p99_ms', 0):8.2f}ms  "
              f"reject {rep['reject_rate']:.2f}  "
              f"cache {rep['cache_hit_rate']:.2f}")
    return {"store": store, "probe": probe, "queries": queries,
            "pool": pool, "closed_loop_capacity_qps": round(capacity, 1),
            "max_batch": max_batch, "max_delay_ms": max_delay_ms,
            "max_pending": max_pending, "loads": rows}


def main() -> None:
    from repro.core.registry import backend_names

    ap = argparse.ArgumentParser()
    ap.add_argument("--store", type=str, default="repair_skip",
                    choices=backend_names())
    ap.add_argument("--probe", type=str, default="vmap",
                    choices=["vmap", "kernel"])
    ap.add_argument("--queries", type=int, default=200,
                    help="queries per offered-load run")
    ap.add_argument("--pool", type=int, default=48,
                    help="distinct queries in the traffic pool")
    ap.add_argument("--loads", type=str, default="0.5,1.0,2.0",
                    help="offered loads as fractions of closed-loop capacity")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--max-pending", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    loads = tuple(float(x) for x in args.loads.split(","))
    report = run(store=args.store, probe=args.probe, queries=args.queries,
                 loads=loads, pool=args.pool, max_batch=args.max_batch,
                 max_delay_ms=args.max_delay_ms, max_pending=args.max_pending,
                 seed=args.seed)
    print(json.dumps({"serving_latency": report}))


if __name__ == "__main__":
    main()
