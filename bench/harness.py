"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the reduction of what was seen to metrics.

A cell is found by name in ``BENCHMARK.json``: its configuration file, its
traffic file ``bench/traffic/<mix>.json`` and one reader per metric,
``bench/metrics/<metric>.py``, whose ``read(run)`` returns the metric's
value from the :class:`Run` record, or ``None`` where it finds nothing to
read.  A cell reads every metric that has no ``workloads`` list and each
one whose list names it.  The configuration's ``index.kind`` picks the
index the program builds and the reference that checks it
(:data:`INDEX_KINDS`: ``nonpositional`` or ``positional``); the traffic
file's ``kind`` picks the queries (``bench/traffic.py``: ``and`` or
``phrase``).  Adding a cell, a configuration or a metric adds files and
entries; it edits none.

The program receives only the generated documents and query strings.  The
window drives ``MicroBatchFrontend.submit`` -> ``Session.execute`` -> the
device servers of a session opened from a persisted index with the
program's defaults; the only setting made is the frontend's
``max_batch``, from the configuration.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

import numpy as np

from . import reference, roofline
from .collection import generate_docs
from .reference import PositionalReference, WordReference, parse
from .trace import (EXECUTE_SPAN, PENDING_SPAN, WINDOW_SPAN, breakdown,
                    find_xplane, reduce_file)
from .traffic import Traffic, make_traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
sys.path.insert(0, str(ROOT / "src"))

from repro.core.index import NonPositionalIndex, PositionalIndex  # noqa: E402


class IndexKind(NamedTuple):
    index_class: type  # the program's index class
    reference: type  # the reference that checks its answers


#: ``index.kind`` of a configuration -> what the harness builds and checks
INDEX_KINDS = {
    "nonpositional": IndexKind(NonPositionalIndex, WordReference),
    "positional": IndexKind(PositionalIndex, PositionalReference),
}


def index_kind(config: dict) -> IndexKind:
    kind = config["index"]["kind"]
    if kind not in INDEX_KINDS:
        raise ValueError(f"unknown index kind {kind!r} in configuration "
                         f"{config['name']!r}; the harness knows "
                         f"{sorted(INDEX_KINDS)}")
    return INDEX_KINDS[kind]


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    chips: int = 1
    root: Path = ROOT  # where its files, metric readers among them, lie


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=workload,
                config=json.loads((root / entry["file"]).read_text()),
                traffic=json.loads((root / BENCH.relative_to(ROOT) / "traffic"
                                    / f"{w['traffic']}.json").read_text()),
                end_to_end=e2e, per_layer=per_layer, chips=int(w["chips"]),
                root=root)


@dataclass
class Run:
    """What one run saw, for the metric readers."""

    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    answered: int = 0
    failed: int = 0
    errors: list[str] = field(default_factory=list)
    latencies_s: list[float] = field(default_factory=list)  # from when due
    exec_spans: list[tuple[float, float]] = field(default_factory=list)
    window: tuple[float, float] = (0.0, 0.0)
    frontend: dict = field(default_factory=dict)
    session: dict = field(default_factory=dict)
    device_bytes: int = 0
    text_bytes: int = 0
    trace: object | None = None  # trace.TraceSummary of a traced run
    step_bytes: int | None = None
    peaks: dict | None = None
    answers: list[tuple[str, np.ndarray]] = field(default_factory=list)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------
# set-up: documents and the persisted index
# ----------------------------------------------------------------------
def build_index(config: dict, seed: int, cache: Path):
    """(documents, reference, session) of a configuration for one seed.

    Every run does the same work: generate the documents, ingest them (build
    the index of the configuration's ``index.kind`` and persist it with the
    program's own format, over whatever an earlier run left at the same
    path) and open the persisted index, as a deployment would.  Nothing is
    reused from an earlier run, so set-up depends on the code under test
    and the seed alone."""
    from repro.core.artifact import save_index
    from repro.serving.session import Session

    kind = index_kind(config)
    t = time.perf_counter()
    docs = generate_docs(config["collection"], seed)
    log(f"  generated {len(docs)} documents in {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    ref = kind.reference(docs)
    log(f"  built the {kind.reference.__name__} in "
        f"{time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    ix = kind.index_class.build(docs, store=config["index"]["store"])
    log(f"  built the {config['index']['store']} "
        f"{kind.index_class.__name__} in {time.perf_counter() - t:.2f} s")
    art = cache / "index" / config["name"]
    shutil.rmtree(art, ignore_errors=True)
    t = time.perf_counter()
    save_index(ix, art)
    del ix
    session = Session.open(art)
    log(f"  persisted and opened the index in {time.perf_counter() - t:.2f} s")
    return docs, ref, session


def server_bytes(session) -> int:
    """Bytes of the arrays of every device server the session holds, each
    array counted once."""
    arrays = {id(a): a for s in (session.server, session.positional_server)
              if s is not None for a in s.arrays.values()}
    return sum(int(a.nbytes) for a in arrays.values())


# ----------------------------------------------------------------------
# the served path
# ----------------------------------------------------------------------
async def _warm(session, cfg, batches: list[list[str]]) -> None:
    from repro.serving.frontend import MicroBatchFrontend

    async with MicroBatchFrontend(session, cfg) as fe:
        for batch in batches:
            await asyncio.gather(*(fe.submit(q) for q in batch))


class Outstanding:
    """Requests sent and not yet answered.  In a traced run a
    ``bench.pending`` span covers the time in which there are any, so the
    trace tells the frontend's wait from the load generator's."""

    def __init__(self, trace: bool):
        self.n, self.trace, self.span = 0, trace, None

    def __enter__(self) -> None:
        if self.n == 0 and self.trace:
            import jax

            self.span = jax.profiler.TraceAnnotation(PENDING_SPAN)
            self.span.__enter__()
        self.n += 1

    def __exit__(self, *exc) -> None:
        self.n -= 1
        if self.n == 0 and self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None


async def _closed_loop(fe, streams, seconds: float, run: Run,
                       outstanding: Outstanding) -> None:
    from repro.serving.frontend import FrontendOverloaded

    loop = asyncio.get_running_loop()
    stop = False

    def close() -> None:
        nonlocal stop
        stop = True

    async def client(stream) -> None:
        for q in stream:
            if stop:
                return
            due = time.perf_counter()
            run.attempted += 1
            try:
                with outstanding:
                    ans = await fe.submit(q)
            except FrontendOverloaded:
                run.failed += 1
                continue
            except Exception as e:  # noqa: BLE001 — counted, and fails the run
                run.failed += 1
                run.errors.append(repr(e))
                continue
            run.latencies_s.append(time.perf_counter() - due)
            run.answers.append((q, ans))
        log("  a client ran out of queries before the window closed")

    loop.call_later(seconds, close)
    await asyncio.gather(*(client(s) for s in streams))


def drive(session, cfg, traffic: Traffic, seconds: float, run: Run,
          trace: bool) -> None:
    """The measured window, with ``Session.execute`` wrapped in a span."""
    import jax
    from repro.serving.frontend import MicroBatchFrontend

    inner = session.execute

    def execute(queries):
        t = time.perf_counter()
        if trace:
            with jax.profiler.TraceAnnotation(EXECUTE_SPAN):
                out = inner(queries)
        else:
            out = inner(queries)
        run.exec_spans.append((t, time.perf_counter()))
        return out

    session.execute = execute

    async def window() -> None:
        fe = MicroBatchFrontend(session, cfg)
        outstanding = Outstanding(trace)
        t_open = time.perf_counter()
        await _closed_loop(fe, traffic.streams, seconds, run, outstanding)
        run.window = (t_open, time.perf_counter())
        await fe.close()
        run.frontend = fe.metrics()

    try:
        if trace:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                asyncio.run(window())
        else:
            asyncio.run(window())
    finally:
        session.execute = inner
    run.window_s = run.window[1] - run.window[0]
    run.answered = len(run.answers)
    run.session = session.metrics()


# ----------------------------------------------------------------------
# correctness: every answer the window returned, against the reference
# ----------------------------------------------------------------------
def check(answers, ref, control: bool = False) -> dict:
    """The numbers compared, each with its limit: every answer against
    ``ref.answer``, the reference of the cell's index kind.  ``control``
    puts the reference's control variant in the program's place
    (``control.py``)."""
    wrong = 0
    for q, ans in answers:
        terms = parse(q)
        want = ref.answer(terms)
        if control:
            ans = ref.answer(terms, cap=reference.CONTROL_CAP)
        wrong += not np.array_equal(np.asarray(ans, np.int64), want)
    return {"wrong_answers": {"value": wrong, "limit": 0}}


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
def read_metric(name: str, run: Run, root: Path = ROOT):
    path = root / BENCH.relative_to(ROOT) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float | None = None, cache: Path = CACHE,
             compile_cache: bool = True, keep_trace: Path | None = None) -> dict:
    """One run; returns the result line's object.  ``keep_trace`` copies
    a traced run's ``.xplane.pb`` there before it is deleted."""
    import jax
    from repro.launch.compile_cache import compile_stats, enable_compile_cache
    from repro.serving.frontend import FrontendConfig

    t_start = time.perf_counter() if t_start is None else t_start
    info = device_info()
    if compile_cache:
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_stats()
    config, spec = cell.config, cell.traffic
    run = Run()

    docs, ref, session = build_index(config, seed, cache)
    run.text_bytes = sum(len(d) for d in docs)
    traffic = make_traffic(spec, ref, seed)
    cfg = FrontendConfig(max_batch=int(config["frontend"]["max_batch"]))
    t = time.perf_counter()
    asyncio.run(_warm(session, cfg, traffic.warmup))
    log(f"  warm-up: {len(traffic.warmup)} batch(es) through the frontend in "
        f"{time.perf_counter() - t:.2f} s")
    run.device_bytes = server_bytes(session)
    traces0, compiles0 = session.jit_traces, compile_stats()

    trace_dir = cache / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    run.setup_s = time.perf_counter() - t_start
    log(f"window opens after {run.setup_s:.2f} s of set-up")
    try:
        drive(session, cfg, traffic, seconds, run, trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = compile_stats()
    new_traces = session.jit_traces - traces0
    log(f"window: {run.answered} answered of {run.attempted} sent in "
        f"{run.window_s:.2f} s; {new_traces} new jit trace(s) and "
        f"{compiles['cache_hits'] + compiles['cache_misses'] - compiles0['cache_hits'] - compiles0['cache_misses']} "
        f"compile(s) inside the window")
    spans_ms = [round(1e3 * (e - s), 1) for s, e in run.exec_spans]
    log(f"  {len(spans_ms)} Session.execute call(s), ms: {spans_ms[:40]}")
    memory_peak = peak_bytes()
    if trace:
        run.peaks = roofline.peaks(info["kind"])
        served = session.index if session.index is not None else session.positional
        run.step_bytes = roofline.sweep_bytes(
            served, [parse(q) for q, _ in run.answers],
            [len(a) for _, a in run.answers])
    del session
    gc.collect()

    t = time.perf_counter()
    checks = check(run.answers, ref)
    if run.errors:
        log(f"  {len(run.errors)} request(s) failed with an error; the first: "
            f"{run.errors[0]}")
    checks["request_errors"] = {"value": len(run.errors), "limit": 0}
    log(f"reference: {len(run.answers)} answers compared in "
        f"{time.perf_counter() - t:.2f} s")

    device = dict(info, memory_peak_bytes=memory_peak)
    result: dict = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
                    "attempted": run.attempted, "failed": run.failed}
    if trace:
        xplane = find_xplane(trace_dir)
        if keep_trace is not None:
            shutil.copy(xplane, keep_trace)
        run.trace = reduce_file(xplane)
        log(f"trace: {run.trace.execute_spans} execute spans; program executions "
            f"in the window by name: {run.trace.modules}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = read_metric(m["name"], run, cell.root)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown(run.trace)
    result["checks"] = checks
    return result
