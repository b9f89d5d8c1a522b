#!/usr/bin/env sh
# Tier-1 smoke wrapper: the ROADMAP verify command plus a headless
# end-to-end serving check. CI-able: exits non-zero on any failure.
#
#   scripts/smoke.sh            # full tier-1 + example + registry check
#   scripts/smoke.sh -k serving # extra args are passed to pytest
set -eu

cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export PYTHONPATH

echo "== tier-1: pytest (CPU; Pallas kernels in interpret mode) =="
JAX_PLATFORMS=cpu python -m pytest -x -q "$@"

echo "== backend registry =="
python scripts/list_backends.py

echo "== unified Session: one backend per family, mixed query batch =="
python - <<'EOF'
import numpy as np
from repro.core.index import NonPositionalIndex, PositionalIndex
from repro.data import generate_collection
from repro.data.text import tokenize
from repro.serving.session import Session

col = generate_collection(n_articles=3, versions_per_article=5,
                          words_per_doc=60, seed=7)
ph = tokenize(col.docs[0])[2:4]
sessions = {}
for store in ("repair_skip", "rlcsa"):  # one inverted, one self-index
    sessions[store] = Session(
        NonPositionalIndex.build(col.docs, store=store),
        positional=PositionalIndex.build(col.docs, store=store))
words = [w for w in sessions["repair_skip"].index.vocab.id_to_token[:12]]
batch = [words[1], f"{words[1]} {words[4]}", '"' + " ".join(ph) + '"',
         f"docs: {words[1]} {words[4]}", 'docs: "' + " ".join(ph) + '"']
results = {s: sess.execute(batch) for s, sess in sessions.items()}
for q, a, b in zip(batch, results["repair_skip"], results["rlcsa"]):
    assert np.array_equal(np.sort(np.asarray(a)), np.sort(np.asarray(b))), q
    rt = sessions["rlcsa"].plan(q)
    print(f"  {q!r:32s} -> {len(np.asarray(a)):3d} hits "
          f"(rlcsa strategy: {rt.strategy})")
m = sessions["rlcsa"].metrics()
assert m["plans_compiled"] <= len(batch), m
print("inverted/self-index answers agree on the mixed batch")
EOF

echo "== end-to-end: examples/serve_queries.py =="
python examples/serve_queries.py

echo "smoke OK"
