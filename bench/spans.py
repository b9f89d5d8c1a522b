"""The program's own spans inside a run's window, and the arithmetic of
the per-layer metrics that read them.

``repro.serving.tracing`` keeps a ring of the served path's finished
spans, timed on ``time.perf_counter`` like the window itself.  The
window's frontend is built after warm-up, so warm-up and compile batches
fall outside.  Every reader returns ``None`` where the program has no
such recorder, no span lies in the window, or the ring dropped some of
the window's spans.  A span record has ``name``, ``id``, ``parent``,
``start``, ``end`` (seconds) and ``attrs``.
"""

from __future__ import annotations

from bench.stats import percentile


def window_spans(run) -> list | None:
    try:
        from repro.serving import tracing
    except ImportError:  # a program without the recorder
        return None
    lo, hi = run.window
    got = tracing.spans(lo, hi)
    if got.dropped or not got.records:
        return None
    return got.records


def _named(records, name: str) -> list:
    return [r for r in records if r.name == name]


def live_lane_share(records) -> float | None:
    """Decoded postings over padded lanes of the sweep windows (%)."""
    windows = _named(records, "engine.window")
    lanes = sum(w.attrs["lanes"] for w in windows)
    if not lanes:
        return None
    return 100.0 * sum(w.attrs["live_lanes"] for w in windows) / lanes


def host_ms_per_window(records) -> float | None:
    """Mean host time of a sweep window (ms): the window's span less the
    wait for its outputs, with each batch's merge shared over its
    windows."""
    windows = _named(records, "engine.window")
    if not windows:
        return None
    waited = sum(r.end - r.start for r in _named(records, "engine.device_wait"))
    merged = sum(r.end - r.start for r in _named(records, "engine.merge"))
    spent = sum(w.end - w.start for w in windows)
    return 1e3 * (spent - waited + merged) / len(windows)


def plan_ms(records) -> float | None:
    """Mean parse, plan and encode time per Session.execute call (ms)."""
    calls = _named(records, "session.execute")
    if not calls:
        return None
    ids = {c.id for c in calls}
    calls = [c for c in calls if c.parent not in ids]  # segments' own calls
    work = _named(records, "session.plan") + _named(records, "engine.encode")
    return 1e3 * sum(r.end - r.start for r in work) / len(calls)


def queue_ms(records) -> float | None:
    """Median wait of a request from submit to its batch starting (ms)."""
    p = percentile([r.end - r.start for r in _named(records, "frontend.queue")], 50)
    return None if p is None else 1e3 * p
