"""The program's own spans and counters, read from a traced run's trace.

The program under test opens ``jax.profiler.TraceAnnotation`` spans at its
layer boundaries (``repro.core.tracing``): ``spjoin.*`` for the join's
phases, ``verify.*`` for the reduce loop's cells and tiles, ``serve.*`` for
a ``DistIndex`` batch, ``index.*`` for the host index. They lie on the host
thread's line of the run's ``.xplane.pb``, on the clock of the device's
``XLA Ops`` events, nested as the program nested them; a span's counters
are its event's metadata stats (``n_hits``, ``mask_elems``, ...).

``from_planes`` reads them from planes (``ProfileData.planes`` or alike);
``for_run`` finds the run's own trace as ``bench/trace.py::read_dir`` does,
under ``.bench_cache/trace/<cell>/``, reads it once per run, and checks that
it is the trace that ``run.reduction`` was made from (the same
``bench.window``). The readers take the joins' and batches' intervals from
``run.reduction``. A program without these spans, or a run without a trace,
gives None, and every reader then reads nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from pathlib import Path

from bench.trace import WINDOW_SPAN, _union

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_cache" / "trace"
PREFIXES = ("spjoin.", "verify.", "serve.", "index.")
# JAX's host events for tracing, lowering, and compiling or loading a program.
RETRACE = re.compile(
    r"^(trace_to_jaxpr_dynamic|trace_to_jaxpr_nounits|lower_sharding_computation"
    r"|backend_compile|backend_compile_and_load)$|Compile|compile|Deserializ"
)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns, host clock of the trace
    end: float
    counts: dict
    parent: int  # index of the enclosing program span, -1 for a root

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def _inside(s: float, e: float, within: list[tuple[float, float]] | None) -> bool:
    return within is None or any(a <= s and e <= b for a, b in within)


@dataclasses.dataclass
class Spans:
    spans: list[Span]  # in order of start
    events: list[tuple[str, float, float]]  # every event on the lines that hold them
    window: tuple[float, float] | None  # the bench.window span

    def named(self, name: str, within: list[tuple[float, float]] | None = None) -> list[Span]:
        """The spans called ``name`` that lie inside one of ``within``."""
        return [sp for sp in self.spans if sp.name == name and _inside(sp.start, sp.end, within)]

    def total(self, name: str, key: str, within=None) -> tuple[float, int]:
        """Σ of counter ``key`` over the spans called ``name`` that carry
        it, and how many carry it."""
        got = [sp.counts[key] for sp in self.named(name, within) if key in sp.counts]
        return float(sum(got)), len(got)

    def covered_s(self, match, within: list[Span]) -> float:
        """Seconds of the spans ``within`` covered by events whose name
        ``match`` accepts (the union, so nested events count once)."""
        matched = [(s, e) for n, s, e in self.events if match(n)]
        total = 0.0
        for sp in within:
            hits = [(max(s, sp.start), min(e, sp.end)) for s, e in matched
                    if e > sp.start and s < sp.end]
            total += sum(e - s for s, e in _union(hits))
        return total * 1e-9


def from_planes(planes) -> Spans:
    """The program spans of the host planes, each with its parent."""
    events: list[tuple[str, float, float]] = []
    raw: list[tuple[float, float, str, dict]] = []
    windows: list[tuple[float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, ev) for ev in line.events]
            windows += [(s, e) for n, s, e, _ in evs if n == WINDOW_SPAN]
            mine = [(s, e, n, dict(ev.stats)) for n, s, e, ev in evs if n.startswith(PREFIXES)]
            if mine:
                raw += mine
                events += [(n, s, e) for n, s, e, _ in evs]
    raw.sort(key=lambda r: (r[0], -r[1]))
    spans: list[Span] = []
    stack: list[int] = []
    for s, e, n, counts in raw:
        while stack and spans[stack[-1]].end < e:
            stack.pop()
        spans.append(Span(n, s, e, counts, stack[-1] if stack else -1))
        stack.append(len(spans) - 1)
    window = (min(s for s, _ in windows), max(e for _, e in windows)) if windows else None
    return Spans(spans=spans, events=events, window=window)


def read_dir(log_dir) -> Spans | None:
    """The spans of the newest ``.xplane.pb`` under ``log_dir``, if any."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    return from_planes(ProfileData.from_file(max(files, key=os.path.getmtime)).planes)


_read: dict = {"reduction": None, "spans": None}


def for_run(run) -> Spans | None:
    """The program spans of the run's trace, read once per run; None where
    the run was not traced or the program opened no span."""
    red = run.reduction
    if red is None:
        return None
    if _read["reduction"] is not red:
        spans = read_dir(TRACE_DIR / run.cell.name)
        ok = spans is not None and spans.spans and spans.window == red.window
        _read.update(reduction=red, spans=spans if ok else None)
    return _read["spans"]


def per_op(run, op_span: str) -> tuple[Spans, list[tuple[float, float]]] | None:
    """The spans and the intervals of the window's operations (``bench.join``
    or ``bench.query_batch``), or None where there is nothing to read."""
    spans = for_run(run)
    ops = run.reduction.spans_named(op_span) if spans is not None else []
    return (spans, ops) if ops else None


def seconds_per_op(run, op_span: str, name: str) -> float | None:
    """Σ seconds of the spans called ``name`` per operation, or None where
    the program opened none."""
    got = per_op(run, op_span)
    if got is None:
        return None
    spans, ops = got
    found = spans.named(name, ops)
    return sum(sp.seconds for sp in found) / len(ops) if found else None

