#!/usr/bin/env python3
"""Serve the index on a TPU end to end, and check every answer.

    python chip_smoke.py [--seed N]      # one chip: main, kernel and mining phases
    python chip_smoke.py --four-chips    # four chips: the document-partitioned mesh only

One process drives the chip.  Collections are generated from ``--seed``.
Every phase compares its answers with the host path over the same indexes
(``Session.build(..., device=False)``) and fails on any difference.

* main    — a non-positional and a positional ``repair_skip`` index behind
  ``Session.build`` at its defaults (fused layout, vmapped probes); a
  mixed batch (AND, phrase, ``docs:``, ``docs: "…"``, ``top<k>:``,
  ``rank<k>:``) goes through ``MicroBatchFrontend`` twice, cold then warm.
  The warm pass must add no jit trace.
* kernels — the same mix with ``probe="kernel"`` on the fused and the
  dense layout; the lowered step must hold a compiled Pallas kernel
  (``tpu_custom_call``), not the interpreter.
* mining  — ``mine_similarity=True`` and an ``rlz`` build: MinHash
  signatures from the ``minhash_sig`` kernel against the NumPy reference,
  and ``similar:`` / ``versions-of:`` answers against a reference mining.
* four chips (``--four-chips`` only) — a ``PartitionedServer`` whose shard
  arrays are placed across a 4-device mesh, against a one-chip
  ``BatchedServer`` and the host path.

Without a TPU (or with fewer chips than the phase needs) the script exits
non-zero and names the platform JAX found: there is no CPU fallback.  The
last line of standard output is the JSON result, printed only when every
check passed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.index import NonPositionalIndex, PositionalIndex  # noqa: E402
from repro.core.rlz_store import RLZ_MINING  # noqa: E402
from repro.core.similarity import SimilarityIndex  # noqa: E402
from repro.core.similarity.minhash import (element_hashes,  # noqa: E402
                                           signature_matrix)
from repro.data import generate_collection  # noqa: E402
from repro.data.queries import sample_traffic  # noqa: E402
from repro.data.text import tokenize  # noqa: E402
from repro.launch.compile_cache import (compile_stats,  # noqa: E402
                                        enable_compile_cache)
from repro.serving.frontend import (FrontendConfig,  # noqa: E402
                                    MicroBatchFrontend, run_open_loop)
from repro.serving.partitioned import PartitionedServer  # noqa: E402
from repro.serving.plan import AND, parse_query  # noqa: E402
from repro.serving.session import Session  # noqa: E402

VERSIONS = 100  # versions per article
WORDS = 200  # words per document
VOCAB_QUERY_WORDS = 300  # AND / docs / top-k / rank terms: the 300 first-seen words

# Sizes, each with the reason it is what it is (printed with the run).
MAIN = dict(np_docs=50_000, pos_docs=2_000, per_kind=16)
MAIN_WHY = {
    "np_docs": f"a versioned archive: articles of {VERSIONS} versions x "
               f"{WORDS} words",
    "pos_docs": "cut from np_docs: the positional host build is superlinear "
                "(13 s at 2,000 docs, 58 s at 4,000 on an 8-core host)",
    "per_kind": "one device batch per kind: the fused window holds "
                "(batch*64, max_phrase) int32, and max_phrase grows with "
                "np_docs (16,384 at 50,000 docs)",
}
KERNELS = dict(docs=300, per_kind=4)
KERNELS_WHY = ("cut from the main path: the fused kernel probe gathers a "
               "max_phrase-wide row per probe (batch*64*max_phrase^2 int32) "
               "and anchor_probe_sliced compares every probe with every anchor")
MINING = dict(docs=2_000, rlz_docs=2_000, probes=8)
FOUR_CHIPS = dict(np_docs=4_000, pos_docs=1_000, per_kind=16)

#: the traffic kinds of the main path, with their sample_traffic mix names
KINDS = ("and", "phrase", "docs", "docs-phrase", "topk", "rank")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def device_info() -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    return info


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chip(s); JAX found platform "
                 f"{info['platform']!r} with {info['count']} device(s). "
                 f"There is no CPU fallback.")


def collection(n_docs: int, seed: int):
    return generate_collection(n_articles=max(1, n_docs // VERSIONS),
                               versions_per_article=VERSIONS,
                               words_per_doc=WORDS, seed=seed)


def timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, time.perf_counter() - t0


def build_indexes(docs: list[str], pos_docs: int):
    np_idx, np_s = timed(NonPositionalIndex.build, docs, store="repair_skip")
    pidx, pos_s = timed(PositionalIndex.build, docs[:pos_docs],
                        store="repair_skip")
    print(f"  built non-positional repair_skip over {len(docs)} docs in "
          f"{np_s:.2f} s; positional over the first {pos_docs} docs in "
          f"{pos_s:.2f} s")
    return np_idx, pidx


def kind_of(q: str) -> str:
    pq = parse_query(q)
    if pq.kind == "docs" and pq.phrase:
        return "docs-phrase"
    return pq.kind


def query_mix(np_idx, pos_docs: list[str], per_kind: int, seed: int,
              kinds=KINDS) -> list[str]:
    """``per_kind`` distinct queries of each kind: two vocabulary words
    per term query, two-token phrases sampled from the positional docs.
    Distinct, so a kind's batch has the same shape through the frontend
    (which coalesces repeats) as through ``Session.execute``."""
    rng = np.random.default_rng(seed)
    words = list(np_idx.vocab.id_to_token[:VOCAB_QUERY_WORDS])
    out = []
    for kind in kinds:
        qs: list[str] = []
        while len(qs) < per_kind:
            qs = list(dict.fromkeys(qs + sample_traffic(
                kind, per_kind, pos_docs, words, rng)))[:per_kind]
        out += qs
    return out


def require_device_routes(session: Session, queries: list[str]) -> Counter:
    device = Counter(kind_of(q) for q in queries
                     if session.plan(q).route == "device")
    for kind in {kind_of(q) for q in queries}:
        check(device[kind] > 0, f"no {kind} query took the device route")
    return device


def serve(session: Session, queries: list[str], max_batch: int) -> list:
    """One pass of ``queries`` through a fresh frontend (an empty result
    cache, so every answer comes from the session), all arriving at once."""
    fe = MicroBatchFrontend(session, FrontendConfig(max_batch=max_batch,
                                                    max_delay=0.05))
    results, report = run_open_loop(session, queries, rate_qps=0.0,
                                    frontend=fe)
    asyncio.run(fe.close())
    check(report["rejected"] == 0, f"frontend rejected {report['rejected']}")
    return results


def compare(label: str, queries: list[str], got: list, want: list) -> None:
    agree, total = Counter(), Counter()
    for q, g, w in zip(queries, got, want):
        k = kind_of(q)
        total[k] += 1
        agree[k] += g is not None and np.array_equal(np.asarray(g),
                                                     np.asarray(w))
    per_kind = " ".join(f"{k}={agree[k]}/{total[k]}" for k in total)
    n_ok = sum(agree.values())
    print(f"  {label}: {n_ok}/{len(queries)} answers equal the host path "
          f"({per_kind})")
    check(n_ok == len(queries), f"{label}: {len(queries) - n_ok} answer(s) "
                                f"differ from the host path")


def server_bytes(server) -> int:
    return sum(int(a.nbytes) for a in server.arrays.values())


def peak_device_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def main_phase(np_docs: int, pos_docs: int, per_kind: int, seed: int) -> dict:
    print(f"[main] Session.build defaults over repair_skip; sizes: "
          + "; ".join(f"{k}={v} ({MAIN_WHY[k]})" for k, v in
                      dict(np_docs=np_docs, pos_docs=pos_docs,
                           per_kind=per_kind).items()))
    col = collection(np_docs, seed)
    np_idx, pidx = build_indexes(col.docs, pos_docs)
    session, build_s = timed(Session.build, np_idx, positional=pidx)
    host = Session.build(np_idx, positional=pidx, device=False)
    for name, srv in (("non-positional", session.server),
                      ("positional", session.positional_server)):
        print(f"  {name} server: layout={srv.layout} probe={srv.probe} "
              f"max_phrase={srv.max_phrase} posting bytes={srv.device_bytes()} "
              f"device bytes={server_bytes(srv)}")
    print(f"  servers placed on the device in {build_s:.2f} s")
    queries = query_mix(np_idx, col.docs[:pos_docs], per_kind, seed)
    routes = require_device_routes(session, queries)
    print(f"  device-routed queries per kind: {dict(routes)}")
    want, host_s = timed(host.execute, queries)
    print(f"  host path: {len(queries)} queries in {host_s:.2f} s")

    batches0 = session.device_batches
    before = compile_stats()
    cold, cold_s = timed(serve, session, queries, per_kind)
    after = compile_stats()
    traces_cold = session.jit_traces
    batches_cold = session.device_batches - batches0
    warm, warm_s = timed(serve, session, queries, per_kind)
    batches_warm = session.device_batches - batches0 - batches_cold
    by_kind: dict[str, list[str]] = {}
    for q in queries:
        by_kind.setdefault(kind_of(q), []).append(q)
    kind_s = {k: timed(session.execute, qs)[1] for k, qs in by_kind.items()}
    new_traces = session.jit_traces - traces_cold
    print(f"  cold pass {cold_s:.2f} s ({traces_cold} jit traces, "
          f"{after['compile_s'] - before['compile_s']:.2f} s compiling, "
          f"{batches_cold} device batches); warm pass {warm_s:.2f} s "
          f"({batches_warm} device batches); {new_traces} new traces since "
          f"the cold pass")
    print("  warm Session.execute per kind: "
          + " ".join(f"{k}={t:.2f}s" for k, t in kind_s.items()))
    compare("main cold", queries, cold, want)
    compare("main warm", queries, warm, want)
    check(new_traces == 0, f"warm pass traced {new_traces} new step(s)")
    check(batches_cold >= len(routes) and batches_warm >= len(routes),
          f"expected >= {len(routes)} device batches per pass, got "
          f"{batches_cold} cold / {batches_warm} warm")
    check(session.metrics()["device_batches"] > 0, "no device batch ran")
    print(f"  peak device bytes: {peak_device_bytes()}")
    return {"queries": len(queries), "device_batches": session.device_batches,
            "new_traces": new_traces}


def kernel_phase(docs: int, per_kind: int, seed: int) -> dict:
    print(f"[kernels] probe='kernel' on the fused and dense layouts; "
          f"{docs} docs, {per_kind} queries per kind ({KERNELS_WHY})")
    col = collection(docs, seed)
    np_idx, pidx = build_indexes(col.docs, docs)
    host = Session.build(np_idx, positional=pidx, device=False)
    queries = query_mix(np_idx, col.docs, per_kind, seed)
    want = host.execute(queries)
    compare("vmap probes (fused)", queries,
            serve(Session.build(np_idx, positional=pidx), queries, per_kind),
            want)
    found = {}
    for layout in ("fused", "dense"):
        session = Session.build(np_idx, positional=pidx, probe="kernel",
                                layout=layout)
        require_device_routes(session, queries)
        compare(f"kernel probes ({layout})", queries,
                serve(session, queries, per_kind), want)
        check(session.device_batches > 0, f"{layout}: no device batch ran")
        srv = session.server
        qt, ql, _ = srv.encode([list(parse_query(queries[0]).terms)], width=2)
        text = srv._step(AND, qt.shape[1]).lower(
            srv.arrays, jnp.asarray(qt), jnp.asarray(ql), 0).as_text()
        found[layout] = "tpu_custom_call" in text
        print(f"  {layout} AND step lowered: tpu_custom_call "
              f"{'present' if found[layout] else 'absent'}")
    missing = [layout for layout, ok in found.items() if not ok]
    check(not missing, f"{', '.join(missing)} kernel step holds no compiled "
                       f"Pallas kernel (interpret mode?)")
    return {"queries": len(queries)}


def doc_term_ids(idx: NonPositionalIndex, docs: list[str]) -> list[np.ndarray]:
    """Each document's analyzed term ids (what mining shingles)."""
    out = []
    for doc in docs:
        terms = (idx.analyzer.normalize(t) for t in tokenize(doc))
        out.append(np.asarray([idx.vocab.get(t) for t in terms
                               if t is not None], dtype=np.int64))
    return out


def mining_phase(docs: int, rlz_docs: int, probes: int, seed: int) -> dict:
    print(f"[mining] minhash_sig on the device: mine_similarity over {docs} "
          f"docs, one rlz build over {rlz_docs} docs")
    col = collection(docs, seed)
    idx, mine_s = timed(NonPositionalIndex.build, col.docs,
                        store="repair_skip", mine_similarity=True)
    sim = idx.similarity
    ref = SimilarityIndex.mine(doc_term_ids(idx, col.docs), sim.config,
                               backend="ref")
    print(f"  mined {sim.n_clusters} clusters in {mine_s:.2f} s "
          f"(purity {sim.purity(col.article_of):.3f})")
    check(np.array_equal(sim.sigs, ref.sigs),
          "mined signatures differ from the NumPy reference")
    check(np.array_equal(sim.labels, ref.labels),
          "mined clusters differ from the reference mining")
    session = Session.build(idx)
    subjects = np.random.default_rng(seed).choice(docs, size=probes,
                                                  replace=False)
    queries = [f"{kind}:{int(d)}" for d in subjects
               for kind in ("similar", "versions-of")]
    got = session.execute(queries)
    want = [ref.similar(int(q.split(":")[1])) if q.startswith("similar")
            else ref.versions_of(int(q.split(":")[1])) for q in queries]
    compare("similar:/versions-of:", queries, got, want)

    rcol = collection(rlz_docs, seed + 1)
    rlz, rlz_s = timed(NonPositionalIndex.build, rcol.docs, store="rlz")
    sets = [element_hashes(rlz.store.get_list(i))
            for i in range(rlz.store.n_lists)]
    kernel_sigs = signature_matrix(sets, RLZ_MINING, backend="kernel")
    check(np.array_equal(kernel_sigs,
                         signature_matrix(sets, RLZ_MINING, backend="ref")),
          "rlz posting-list signatures differ from the NumPy reference")
    print(f"  rlz build over {rlz_docs} docs in {rlz_s:.2f} s "
          f"({rlz.store.n_heads} heads); {len(sets)} list signatures equal "
          f"the reference")
    # layout="auto" would pick the dense layout for this non-Re-Pair store,
    # whose expand rows widen to max_phrase: a 16-query AND step then
    # gathers (16*64*max_phrase, max_phrase) int32, 20 GB at 2,000 docs
    rsess = Session.build(rlz, layout="fused")
    rq = query_mix(rlz, rcol.docs, MAIN["per_kind"], seed, kinds=("and",))
    compare("rlz AND", rq, rsess.execute(rq),
            Session.build(rlz, device=False).execute(rq))
    return {"clusters": sim.n_clusters, "rlz_heads": rlz.store.n_heads}


def four_chip_phase(np_docs: int, pos_docs: int, per_kind: int, seed: int,
                    n_chips: int = 4) -> dict:
    devices = jax.devices()[:n_chips]
    mesh = jax.make_mesh((n_chips,), ("data",), devices=devices)
    print(f"[four chips] PartitionedServer over a {n_chips}-device mesh; "
          f"{np_docs} docs non-positional, {pos_docs} positional, "
          f"{per_kind} AND + {per_kind} phrase queries")
    col = collection(np_docs, seed)
    np_idx, pidx = build_indexes(col.docs, pos_docs)
    servers = {name: PartitionedServer.from_index(ix, n_shards=n_chips,
                                                  mesh=mesh)
               for name, ix in (("non-positional", np_idx),
                                ("positional", pidx))}
    session = Session(np_idx, positional=pidx,
                      server=servers["non-positional"],
                      positional_server=servers["positional"])
    one_chip = Session.build(np_idx, positional=pidx)
    host = Session.build(np_idx, positional=pidx, device=False)
    queries = query_mix(np_idx, col.docs[:pos_docs], per_kind, seed,
                        kinds=("and", "phrase"))
    require_device_routes(session, queries)
    got = serve(session, queries, per_kind)
    compare("partitioned vs host", queries, got, host.execute(queries))
    compare("partitioned vs one-chip BatchedServer", queries, got,
            one_chip.execute(queries))
    per_device: Counter = Counter()
    for name, srv in servers.items():
        for arr in srv.pidx.arrays.values():
            for shard in arr.addressable_shards:
                per_device[shard.device] += int(shard.data.nbytes)
                check(shard.data.shape[0] == 1,
                      f"{name}: a device holds {shard.data.shape[0]} shards")
    for dev in devices:
        print(f"  bytes on {dev}: {per_device[dev]}")
    check(set(per_device) == set(devices),
          f"shard arrays sit on {len(per_device)} of {n_chips} devices")
    return {"bytes_per_device": [per_device[d] for d in devices]}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the document-partitioned four-chip phase")
    args = ap.parse_args(argv)
    info = device_info()
    require_tpu(info, 4 if args.four_chips else 1)
    print(f"compile cache: {enable_compile_cache()}")
    compile_stats()  # start counting compiles from here
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(seed=args.seed, **FOUR_CHIPS)
    else:
        for phase, sizes in ((main_phase, MAIN), (kernel_phase, KERNELS),
                             (mining_phase, MINING)):
            _, phase_s = timed(phase, seed=args.seed, **sizes)
            print(f"  {phase.__name__}: {phase_s:.2f} s")
    stats = compile_stats()
    print(f"total {time.perf_counter() - t0:.2f} s; compiling "
          f"{stats['compile_s']:.2f} s; persistent cache "
          f"{stats['cache_hits']} hits / {stats['cache_misses']} misses; "
          f"peak device bytes {peak_device_bytes()}")
    print(json.dumps({"ok": True, "device": {"platform": info["platform"],
                                             "kind": info["kind"],
                                             "count": info["count"]}}))


if __name__ == "__main__":
    main()
