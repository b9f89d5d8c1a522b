"""Scale benchmark: mmap open latency + resident bytes, q/s under compaction.

The storage layer's two claims, measured on a synthetic versioned
collection ~100× the test-suite sizes (streamed into a multi-segment
:class:`~repro.core.writer.IndexWriter` by :mod:`repro.data.synthetic` —
the collection is never materialized):

* **open cost** — ``Session.open(..., mmap=True)`` vs the eager open on
  the same multi-segment artifact, each probed in a *fresh subprocess*
  (clean page cache attribution, no allocator reuse): wall-clock open
  latency, resident-set growth across the open, and the fraction of
  artifact bytes materialized.  The mmap open must not pay the
  per-list re-encode the eager restore pays, so it should be ≥10×
  faster with resident growth a small fraction of the artifact.

* **serving under background compaction** — a mixed query batch served
  while :meth:`~repro.core.writer.IndexWriter.compact_async` merges all
  segments behind the session, vs the same batch quiesced; every answer
  during and after the swap must be byte-identical to the quiesced
  answers (checked, not assumed).

Emits a JSON object on stdout after the human-readable report (the
``record_bench.py`` contract).

    PYTHONPATH=src python benchmarks/scale_open.py            # full scale
    PYTHONPATH=src python benchmarks/scale_open.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np


def _rss_bytes() -> int:
    """Resident set size of this process (Linux /proc; 0 elsewhere)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def _artifact_bytes(writer_dir: Path) -> int:
    return sum(p.stat().st_size
               for p in writer_dir.rglob("*") if p.is_file())


def _sample_queries(session) -> list[str]:
    """A deterministic mixed batch over the served vocabulary — identical
    across probes of the same artifact (the differential anchor)."""
    words = [w for w in session.primary_index.vocab.id_to_token
             if w.isalpha()][:64]
    queries: list[str] = []
    for i in range(0, len(words) - 1, 4):
        queries.append(words[i])
        queries.append(f"{words[i]} {words[i + 1]}")
        queries.append(f"top10: {words[i]}")
        queries.append(f"docs: {words[i + 1]}")
    return queries


def _answers_digest(results) -> str:
    h = hashlib.sha256()
    for r in results:
        h.update(np.ascontiguousarray(np.asarray(r, dtype=np.int64)).tobytes())
    return h.hexdigest()


# ----------------------------------------------------------------------
# subprocess probe: open one way, report latency / residency / answers
# ----------------------------------------------------------------------
def _probe(writer_dir: str, mmap: bool) -> None:
    from repro.serving.session import Session

    # pre-warm the lazy imports Session.open would otherwise pull in, so
    # the probe times the open itself, not Python module loading
    import repro.core.backends  # noqa: F401
    import repro.core.registry  # noqa: F401
    import repro.serving.engine  # noqa: F401

    base_rss = _rss_bytes()
    t0 = time.perf_counter()
    session = Session.open(writer_dir, device=False, mmap=mmap)
    open_s = time.perf_counter() - t0
    rss_open = _rss_bytes() - base_rss
    queries = _sample_queries(session)
    t0 = time.perf_counter()
    results = session.execute(queries)
    query_s = time.perf_counter() - t0
    rss_query = _rss_bytes() - base_rss
    stores = [seg.session.index.blobstore for seg in session._segments]
    print(json.dumps({
        "open_s": open_s,
        "query_s": query_s,
        "rss_open_bytes": rss_open,
        "rss_query_bytes": rss_query,
        "loaded_fraction": round(
            sum(b.loaded_nbytes for b in stores)
            / max(1, sum(b.total_nbytes for b in stores)), 4),
        "digest": _answers_digest(results),
        "n_queries": len(queries),
    }))


def _run_probe(writer_dir: Path, mmap: bool) -> dict:
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if "PYTHONPATH" in env else "")
    # the probe serves on the host (device=False); keeping it off the
    # accelerator leaves the chip to the parent process, which holds it
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--probe-dir",
         str(writer_dir)] + (["--probe-mmap"] if mmap else []),
        capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


# ----------------------------------------------------------------------
# the benchmark
# ----------------------------------------------------------------------
def run(n_articles: int = 160, versions: int = 100, words: int = 150,
        commit_docs: int = 2000, store: str = "vbyte", seed: int = 0,
        workdir: str | None = None) -> dict:
    from repro.core.writer import IndexWriter
    from repro.data.synthetic import SyntheticSpec, ingest_stream
    from repro.serving.session import Session

    spec = SyntheticSpec(n_articles=n_articles, versions_per_article=versions,
                         words_per_doc=words, chunk_docs=commit_docs,
                         seed=seed)
    root = Path(workdir or tempfile.mkdtemp(prefix="scale_open_"))
    writer_dir = root / "ix"
    try:
        t0 = time.perf_counter()
        writer = IndexWriter(writer_dir, store=store, positional=False)
        n_docs = ingest_stream(writer, spec)
        ingest_s = time.perf_counter() - t0
        artifact = _artifact_bytes(writer_dir)
        n_segments = len(writer.segments)

        eager = _run_probe(writer_dir, mmap=False)
        mapped = _run_probe(writer_dir, mmap=True)
        if eager["digest"] != mapped["digest"]:
            raise AssertionError(
                "mmap answers diverge from eager answers — the mapped "
                "store is not serving the persisted lists")

        # serving during background compaction vs quiesced
        session = Session.open(writer_dir, device=False, mmap=True)
        queries = _sample_queries(session)
        expected = _answers_digest(session.execute(queries))  # warm + anchor
        t0 = time.perf_counter()
        n_quiesced = 0
        while time.perf_counter() - t0 < 1.0:
            session.execute(queries)
            n_quiesced += 1
        qps_quiesced = n_quiesced * len(queries) / (time.perf_counter() - t0)

        handle = writer.compact_async(on_swap=session.refresh)
        t0 = time.perf_counter()
        n_during = 0
        identical = True
        while not handle.done:
            identical &= _answers_digest(session.execute(queries)) == expected
            n_during += 1
        during_s = time.perf_counter() - t0
        handle.wait(600)
        qps_during = (n_during * len(queries) / during_s) if n_during else 0.0
        identical &= _answers_digest(session.execute(queries)) == expected
        assert len(session._segments) == 1  # the swap reached the session
    finally:
        if workdir is None:
            shutil.rmtree(root, ignore_errors=True)

    speedup = eager["open_s"] / max(mapped["open_s"], 1e-9)
    report = {
        "store": store,
        "n_docs": n_docs,
        "n_segments": n_segments,
        "artifact_bytes": artifact,
        "ingest_s": round(ingest_s, 2),
        "open_eager_s": round(eager["open_s"], 4),
        "open_mmap_s": round(mapped["open_s"], 4),
        "open_speedup": round(speedup, 1),
        "rss_eager_open_bytes": eager["rss_open_bytes"],
        "rss_mmap_open_bytes": mapped["rss_open_bytes"],
        "rss_mmap_query_bytes": mapped["rss_query_bytes"],
        "mmap_loaded_fraction": mapped["loaded_fraction"],
        "qps_quiesced": round(qps_quiesced, 1),
        "qps_during_compaction": round(qps_during, 1),
        "batches_during_compaction": n_during,
        "during_compaction_identical": bool(identical),
    }
    mb = 1 / (1024 * 1024)
    print(f"{store}: {n_docs} docs in {n_segments} segments, "
          f"artifact {artifact * mb:.1f} MB (ingest {ingest_s:.1f}s)")
    print(f"open: eager {eager['open_s']:.3f}s / mmap {mapped['open_s']:.4f}s "
          f"= {speedup:.0f}x; RSS growth eager "
          f"{eager['rss_open_bytes'] * mb:.1f} MB vs mmap "
          f"{mapped['rss_open_bytes'] * mb:.1f} MB "
          f"(loaded fraction {mapped['loaded_fraction']:.3f})")
    print(f"serving: {qps_quiesced:.0f} q/s quiesced, {qps_during:.0f} q/s "
          f"during background compaction "
          f"({n_during} batches, identical={identical})")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (same pipeline, reduced collection)")
    ap.add_argument("--store", type=str, default="vbyte")
    ap.add_argument("--articles", type=int, default=None)
    ap.add_argument("--versions", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", type=str, default=None)
    ap.add_argument("--probe-dir", type=str, default=None,
                    help=argparse.SUPPRESS)  # internal: subprocess probe
    ap.add_argument("--probe-mmap", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.probe_dir is not None:
        _probe(args.probe_dir, mmap=args.probe_mmap)
        return
    if args.smoke:
        articles, versions, commit_docs = 12, 30, 60
    else:
        articles, versions, commit_docs = 160, 100, 2000
    if args.articles is not None:
        articles = args.articles
    if args.versions is not None:
        versions = args.versions
    report = run(n_articles=articles, versions=versions,
                 commit_docs=commit_docs, store=args.store, seed=args.seed,
                 workdir=args.workdir)
    print(json.dumps({"scale_open": report}))


if __name__ == "__main__":
    main()
