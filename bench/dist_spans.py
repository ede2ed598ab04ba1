"""The distributed join's own spans (``dist.*``), read from a traced run's
trace.

``distributed.distributed_join`` opens ``dist.join`` around each join, with
``dist.put``, ``dist.sample``, ``dist.plan``, ``dist.stage``,
``dist.readback`` and ``dist.merge`` inside it (``repro.core.tracing``).
They lie on the host thread's line of the run's ``.xplane.pb``, on the
clock of the device's ``XLA Ops`` events; a span's counters are its
event's metadata stats. ``program_spans`` reads the other layers' spans
the same way.

``for_run`` reads the run's own trace once per run, and checks that it is
the trace that ``run.reduction`` was made from (the same
``bench.window``). A program without these spans, or a run without a
trace, gives None, and every reader then reads nothing.
"""
from __future__ import annotations

import glob
import os

from bench import program_spans
from bench.trace import WINDOW_SPAN

PREFIX = "dist."


def from_planes(planes) -> program_spans.Spans:
    """The ``dist.*`` spans of the host planes, each with its parent."""
    raw: list[tuple[float, float, str, dict]] = []
    events: list[tuple[str, float, float]] = []
    windows: list[tuple[float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, ev) for ev in line.events]
            windows += [(s, e) for n, s, e, _ in evs if n == WINDOW_SPAN]
            mine = [(s, e, n, dict(ev.stats)) for n, s, e, ev in evs if n.startswith(PREFIX)]
            if mine:
                raw += mine
                events += [(n, s, e) for n, s, e, _ in evs]
    raw.sort(key=lambda r: (r[0], -r[1]))
    spans: list[program_spans.Span] = []
    stack: list[int] = []
    for s, e, n, counts in raw:
        while stack and spans[stack[-1]].end < e:
            stack.pop()
        spans.append(program_spans.Span(n, s, e, counts, stack[-1] if stack else -1))
        stack.append(len(spans) - 1)
    window = (min(s for s, _ in windows), max(e for _, e in windows)) if windows else None
    return program_spans.Spans(spans=spans, events=events, window=window)


def read_dir(log_dir) -> program_spans.Spans | None:
    """The ``dist.*`` spans of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    return from_planes(ProfileData.from_file(max(files, key=os.path.getmtime)).planes)


_read: dict = {"reduction": None, "spans": None}


def for_run(run) -> program_spans.Spans | None:
    """The run's ``dist.*`` spans, read once per run; None where the run
    was not traced or the program opened no such span."""
    red = run.reduction
    if red is None:
        return None
    if _read["reduction"] is not red:
        spans = read_dir(program_spans.TRACE_DIR / run.cell.name)
        ok = spans is not None and spans.spans and spans.window == red.window
        _read.update(reduction=red, spans=spans if ok else None)
    return _read["spans"]


def per_join(run) -> tuple[program_spans.Spans, list[tuple[float, float]]] | None:
    """The spans and the intervals of the window's joins (``bench.join``),
    or None where there is nothing to read."""
    spans = for_run(run)
    joins = run.reduction.spans_named("bench.join") if spans is not None else []
    return (spans, joins) if joins else None


def seconds_per_join(run, *names: str) -> float | None:
    """Σ seconds of the spans called any of ``names`` per join, or None
    where the program opened none."""
    got = per_join(run)
    if got is None:
        return None
    spans, joins = got
    found = [sp for name in names for sp in spans.named(name, joins)]
    return sum(sp.seconds for sp in found) / len(joins) if found else None
