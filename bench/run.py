#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are the ``workloads`` of ``BENCHMARK.json``.  The run fails, and
prints no result, where JAX finds no TPU or fewer chips than the cell
needs: there is no CPU fallback.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
window.  The numbers compared with the reference end standard error, one
per line beside its limit; the last line of standard output is the
result as one JSON object.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.harness import device_info, load_cell, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    info = device_info()
    print(f"device: platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu" or info["count"] < cell.chips:
        print(f"bench/run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found platform {info['platform']!r} with {info['count']} "
              f"device(s). There is no CPU fallback.", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
