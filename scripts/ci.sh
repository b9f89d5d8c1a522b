#!/usr/bin/env sh
# CI entry point: tier-1 tests, the end-to-end smoke checks, and the
# cross-backend differential suite under a fixed seed (deterministic runs;
# override with REPRO_DIFF_SEED=<n> to fuzz a different collection).
#
#   scripts/ci.sh                      # full gate
#   REPRO_DIFF_SEED=123 scripts/ci.sh  # same gate, different fuzz seed
set -eu

cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export PYTHONPATH
REPRO_DIFF_SEED=${REPRO_DIFF_SEED:-20260727}
export REPRO_DIFF_SEED

# tier-1 plus the differential suite exceed the CI budget single-process;
# run them in parallel without dropping a single test: pytest-xdist when the
# environment has it, otherwise a shell-level fan-out over disjoint file
# buckets (size-ordered round-robin as a duration proxy; the differential
# suite gets a bucket of its own).  The tests run on the CPU (Pallas kernels
# in interpret mode): on a machine with a TPU, parallel workers would
# otherwise compete for the one chip.
PYTEST_BUCKETS=${PYTEST_BUCKETS:-4}
if python -c "import xdist" 2> /dev/null; then
    echo "== tier-1 + differential: pytest -n auto (xdist, seed $REPRO_DIFF_SEED) =="
    JAX_PLATFORMS=cpu python -m pytest -q -n auto
else
    echo "== tier-1 + differential: $PYTEST_BUCKETS+1 parallel pytest buckets (seed $REPRO_DIFF_SEED) =="
    BUCKET_DIR=$(mktemp -d)
    i=0
    for f in $(ls -S tests/test_*.py); do
        [ "$f" = "tests/test_differential.py" ] && continue
        echo "$f" >> "$BUCKET_DIR/bucket$((i % PYTEST_BUCKETS)).lst"
        i=$((i + 1))
    done
    # the differential suite is the single slowest file: its own bucket
    echo tests/test_differential.py > "$BUCKET_DIR/bucket$PYTEST_BUCKETS.lst"
    pids=""
    b=0
    while [ "$b" -le "$PYTEST_BUCKETS" ]; do
        # shellcheck disable=SC2046
        JAX_PLATFORMS=cpu python -m pytest -q --basetemp="$BUCKET_DIR/tmp$b" \
            $(tr '\n' ' ' < "$BUCKET_DIR/bucket$b.lst") \
            > "$BUCKET_DIR/bucket$b.log" 2>&1 &
        pids="$pids $!"
        b=$((b + 1))
    done
    fail=0
    b=0
    for pid in $pids; do
        if ! wait "$pid"; then
            fail=1
            echo "-- bucket $b FAILED ($(tr '\n' ' ' < "$BUCKET_DIR/bucket$b.lst")) --"
            cat "$BUCKET_DIR/bucket$b.log"
        else
            tail -n 1 "$BUCKET_DIR/bucket$b.log"
        fi
        b=$((b + 1))
    done
    rm -rf "$BUCKET_DIR"
    [ "$fail" -eq 0 ] || { echo "pytest buckets failed"; exit 1; }
fi

echo "== smoke: registry + engine + example (fast pytest subset) =="
sh scripts/smoke.sh -k "registry or codecs or doclist"

echo "== explain CLI: physical plans against one backend per family =="
python scripts/explain.py "top5: alpha beta" --store repair_skip
python scripts/explain.py --sample docs-phrase --store rlcsa --json
python scripts/explain.py --operators

echo "== index lifecycle: build -> persist -> open -> serve -> ingest =="
python scripts/list_backends.py --require persist > /dev/null
LIFECYCLE_DIR=$(mktemp -d)
trap 'rm -rf "$LIFECYCLE_DIR"' EXIT INT TERM
python scripts/lifecycle_smoke.py "$LIFECYCLE_DIR"

echo "== version mining: clusters -> rlz backend -> similar: queries =="
python scripts/list_backends.py --require referential > /dev/null
python - <<'PY'
import numpy as np
from repro.core.index import NonPositionalIndex
from repro.data import generate_collection
from repro.serving.session import Session

col = generate_collection(n_articles=3, versions_per_article=6,
                          words_per_doc=80, structure="tree", seed=5)
idx = NonPositionalIndex.build(col.docs, store="rlz", mine_similarity=True)
assert idx.similarity.purity(col.article_of) >= 0.9, "mined clusters impure"
s = Session(idx)
hits = s.execute("similar: 0")
assert len(hits) and 0 not in hits, f"similar:0 smoke answer {hits}"
versions = s.execute("versions-of: 0")
assert 0 in versions and set(hits) <= set(versions.tolist()), \
    f"versions-of:0 {versions} does not cover similar:0 {hits}"
print(f"version mining OK: {idx.similarity.n_clusters} clusters, "
      f"{idx.store.n_heads} rlz heads, similar:0 -> {len(hits)} docs")
PY

echo "== serving frontier: record benchmark runs into BENCH_*.json =="
# small configurations — the point is the recorded trajectory (every CI
# run appends its numbers next to its predecessors'), not peak load
python benchmarks/serving_latency.py --store vbyte --queries 120 --pool 32 \
    | python scripts/record_bench.py BENCH_serving.json
python benchmarks/ingest_throughput.py --store vbyte --commits 4 --batch 60 \
    --workdir "$LIFECYCLE_DIR/ingest_bench" \
    | python scripts/record_bench.py BENCH_ingest.json
python benchmarks/ranked_throughput.py --store vbyte --repeats 2 \
    | python scripts/record_bench.py BENCH_serving.json
# scale smoke: reduced-scale synthetic stream -> mmap open vs eager (the
# probes differentially spot-check answers) -> q/s during background
# compaction with byte-identity asserted across the swap
python benchmarks/scale_open.py --smoke \
    | python scripts/record_bench.py BENCH_ingest.json
python benchmarks/compression_ratio.py \
    | python scripts/record_bench.py BENCH_compression.json

echo "ci OK"
