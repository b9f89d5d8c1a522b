"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
and the ``breakdown`` read.

The benchmark opens three kinds of host span with
``jax.profiler.TraceAnnotation``: one ``bench.window`` around the measured
window, one ``bench.execute`` around each ``Session.execute`` call, and
``bench.pending`` over each stretch of time in which some request has been
sent and not yet answered.
On each device plane (``/device:...``) the line ``XLA Modules`` holds one
event per program execution and ``XLA Ops`` one per operation; where a
plane has no op line, its module events stand for its ops.

* busy: the union of the op intervals inside the window, per device;
* program executions and their device time inside the window;
* idle gaps: the intervals of the window in which no op runs on the first
  device, each named by the benchmark span open on the host at the gap's
  midpoint: ``execute`` (the host is inside ``Session.execute``), else
  ``frontend`` (requests wait in or for the frontend), else ``load
  generator`` (no request is outstanding: the host waits for the next
  arrival).
"""

from __future__ import annotations

from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
EXECUTE_SPAN = "bench.execute"
PENDING_SPAN = "bench.pending"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over devices
    devices: int
    executions: int  # program executions on the first device
    execution_s: float  # their summed device time
    execute_spans: int
    modules: dict[str, int] = field(default_factory=dict)  # executions by program name
    op_s: dict[str, float] = field(default_factory=dict)  # first device
    idle_gaps: list[tuple[str, float]] = field(default_factory=list)  # longest first


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _covered(t: float, spans: list[tuple[float, float]]) -> bool:
    return any(s <= t <= e for s, e in spans)


def reduce_planes(planes) -> TraceSummary:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    host_spans: dict[str, list[tuple[float, float]]] = defaultdict(list)
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = lines.get("XLA Modules", [])
            ops = lines.get("XLA Ops", modules)
            if modules or ops:
                devices.append((plane.name, modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (WINDOW_SPAN, EXECUTE_SPAN, PENDING_SPAN):
                        host_spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    if not host_spans[WINDOW_SPAN]:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace holds no device plane with events")
    lo, hi = max(host_spans[WINDOW_SPAN], key=lambda w: w[1] - w[0])
    window_ns = hi - lo
    devices.sort(key=lambda d: d[0])
    busy = []
    for _, _, ops in devices:
        iv = _union(_clip([(e.start_ns, e.start_ns + e.duration_ns) for e in ops],
                          lo, hi))
        busy.append(sum(e - s for s, e in iv))
    _, modules, ops = devices[0]
    inside = [e for e in modules if lo <= e.start_ns < hi]
    op_s: dict[str, float] = defaultdict(float)
    for e in ops:
        if lo <= e.start_ns < hi:
            op_s[e.name] += e.duration_ns / 1e9
    execs = _clip(host_spans[EXECUTE_SPAN], lo, hi)
    pending = _clip(host_spans[PENDING_SPAN], lo, hi)
    gaps = []
    cursor = lo
    for s, e in _union(_clip([(e.start_ns, e.start_ns + e.duration_ns) for e in ops],
                             lo, hi)) + [(hi, hi)]:
        if s > cursor:
            mid = (cursor + s) / 2
            host = ("execute" if _covered(mid, execs) else
                    "frontend" if _covered(mid, pending) else "load generator")
            gaps.append((host, (s - cursor) / 1e9))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=window_ns / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        devices=len(devices),
        executions=len(inside),
        execution_s=sum(e.duration_ns for e in inside) / 1e9,
        execute_spans=len(execs),
        modules=dict(Counter(e.name for e in inside)),
        op_s=dict(sorted(op_s.items(), key=lambda kv: -kv[1])),
        idle_gaps=gaps)


def reduce_file(path) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)


def breakdown(summary: TraceSummary, n: int = 10) -> dict:
    return {"device_ops": [[k, v] for k, v in list(summary.op_s.items())[:n]],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:n]]}
