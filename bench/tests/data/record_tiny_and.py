#!/usr/bin/env python3
"""Record ``tiny_and.xplane.pb``, a TPU trace for a test of the reduction
on a real trace: a traced run of a tiny AND cell (3 articles of 10
versions, 4 closed-loop clients of 3 rounds).  It prints the result line,
whose log names the programs counted per ``Session.execute`` call.  Run it
on a machine with a TPU:

    python3 bench/tests/data/record_tiny_and.py
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2]))

from bench.harness import CACHE, Cell, run_cell  # noqa: E402

CONFIG = {"name": "tiny-archive",
          "collection": {"articles": 3, "versions_per_article": 10,
                         "words_per_doc": 30, "vocab_size": 60, "zipf": 1.1,
                         "edit_rate": 0.05, "structure": "linear", "vocab_seed": 7},
          "index": {"kind": "nonpositional", "store": "repair_skip"},
          "frontend": {"max_batch": 4}}
TRAFFIC = {"kind": "and", "terms": 2, "words": "low", "loop": "closed",
           "clients": 4, "rounds": 3, "warmup": [4]}

if __name__ == "__main__":
    per_layer = [{"name": n, "unit": u} for n, u in [
        ("frontend.overhead_share.sweep", "%"), ("session.execute_ms.sweep", "ms"),
        ("engine.steps_per_batch.sweep", "steps"), ("device.idle_share.sweep", "%"),
        ("serve_step_roofline", "%")]]
    cell = Cell("tiny.and", CONFIG, TRAFFIC, [], per_layer)
    result = run_cell(cell, seed=5, seconds=30.0, trace=True, cache=CACHE / "tiny",
                      keep_trace=HERE / "tiny_and.xplane.pb")
    print(json.dumps(result))
