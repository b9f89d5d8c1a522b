#!/usr/bin/env python3
"""Read the control at a cell's own size: the reference put in the
program's place with its one guarantee broken, the way a later change might
be tempted to break it.  The limit of ``wrong_answers`` lies between the
largest reading of sound runs and the smallest reading printed here.

    python3 bench/control.py --workload wiki-archive.and-low --seeds 1,2,3

The control cuts each AND query's driving list (its shorter list), or each
phrase's rarest word's position list, to its first
``reference.CONTROL_CAP`` entries, as a sweep with a fixed candidate
budget would answer; the reading is the count of answers that differ.  The
queries are the closed loop's first ``--rounds`` rounds, about what one run
answers.  The benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.collection import generate_docs  # noqa: E402
from bench.harness import check, index_kind, load_cell  # noqa: E402
from bench.reference import parse  # noqa: E402
from bench.traffic import make_traffic  # noqa: E402


def readings(cell, seed: int, rounds: int) -> dict:
    ref = index_kind(cell.config).reference(
        generate_docs(cell.config["collection"], seed))
    streams = make_traffic(cell.traffic, ref, seed).streams
    queries = [q for r in range(rounds) for s in streams for q in s[r:r + 1]]
    checks = check([(q, None) for q in queries], ref, control=True)
    lens = sorted(min(ref.df(w) for w in parse(q)) for q in queries)
    return {"seed": seed, "queries": len(queries),
            "driving_df_median": lens[len(lens) // 2], "driving_df_max": lens[-1],
            **{k: v["value"] for k, v in checks.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rounds", type=int, default=4,
                    help="closed loop: rounds of queries a run answers")
    args = ap.parse_args()
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = readings(cell, seed, args.rounds)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
