"""Reduction of a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
with ``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
on each, the ``XLA Ops`` line holds one event per operation that ran, with
its device start and duration on the host's clock, and the ``XLA Modules``
line one event per compiled program that ran; an operation belongs to the
program whose event holds its start. The benchmark's own host
spans (``jax.profiler.TraceAnnotation`` named ``bench.*``) lie on a host
thread's line. The window is the ``bench.window`` span.

An op's event name is its HLO instruction (``%pairdist_filtered_blocked.1
= s8[...] custom-call(...)``); ``op_name`` keeps the instruction's name
without ``%`` and the ``.N`` suffix, so a Pallas kernel reads as the name
of its ``pallas_call`` (``pairdist_filtered_blocked``, ``map_assign_blocked``).

- busy: the union of the device's operation intervals inside the window,
  averaged over the device planes; idle share is 1 − busy / window;
- device time by op name, inside the window;
- idle time by what the host was doing: each stretch of the window in
  which no operation ran is labelled with the innermost event around its
  middle on the host thread that holds the benchmark's spans (the
  runtime's own events there, such as ``np.asarray(jax.Array)`` for a
  readback or ``PjitFunction(<name>)`` for a dispatch, or else the
  benchmark's span: untraced Python), and summed by label.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]  # ns, host clock
    busy_s: float  # mean over device planes
    op_s: dict[str, float]  # device seconds by op name, summed over planes
    ops: list[list[tuple[str, str, float, float]]]  # per plane: (op, program, start_ns, end_ns)
    spans: list[tuple[str, float, float]]  # benchmark host spans
    gaps: list[tuple[str, float]]  # (label, idle seconds summed), most first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name]

    def device_s(self, match, within: list[tuple[float, float]] | None = None) -> float:
        """Device seconds, summed over planes, of ops for which
        ``match(op_name, program_name)`` holds, clipped to the intervals
        ``within`` (default: the window)."""
        spans = within if within is not None else [self.window]
        total = 0.0
        for plane in self.ops:
            for name, module, s, e in plane:
                if match(name, module):
                    for a, b in spans:
                        total += max(0.0, min(e, b) - max(s, a))
        return total * 1e-9


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """``%pairdist_filtered_blocked.1 = s8[...] custom-call(...)`` →
    ``pairdist_filtered_blocked``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _labels(events: list[tuple[str, float, float]], points: list[float]) -> list[str]:
    """For each time point, the name of the innermost event containing it
    (events on one thread nest), or the window's name where none does."""
    order = sorted(range(len(points)), key=points.__getitem__)
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [WINDOW_SPAN] * len(points)
    stack: list[tuple[str, float, float]] = []
    k = 0
    for i in order:
        t = points[i]
        while k < len(evs) and evs[k][1] <= t:
            while stack and stack[-1][2] < evs[k][1]:
                stack.pop()
            stack.append(evs[k])
            k += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        if stack:
            out[i] = stack[-1][0]
    return out


def _attribute(ops, modules) -> list[tuple[str, str, float, float]]:
    """Each op with the name of the program whose interval holds its start."""
    starts = [m[0] for m in modules]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        module = modules[k][2] if k >= 0 and s < modules[k][1] else ""
        out.append((name, module, s, e))
    return out


def reduce_planes(planes) -> Reduction:
    """Reduce planes (``ProfileData.planes`` or alike) to a Reduction."""
    spans: list[tuple[str, float, float]] = []
    host: list[tuple[str, float, float]] = []  # events of the thread that holds the spans
    device_ops: list[list[tuple[str, str, float, float]]] = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
            device_ops.append(_attribute(ops, sorted(modules)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
                mine = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                if mine:
                    spans += mine
                    host += evs
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    if not device_ops:
        raise ValueError("trace holds no TPU device plane")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    op_s: dict[str, float] = {}
    busy = 0.0
    idle: list[tuple[float, float]] = []
    for ops in device_ops:
        clipped = [(n, max(s, w0), min(e, w1)) for n, _, s, e in ops if e > w0 and s < w1]
        for n, s, e in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        idle += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    by_label: dict[str, float] = {}
    inside = [e for e in host if e[0] != WINDOW_SPAN]
    for label, (a, b) in zip(_labels(inside, [(a + b) / 2 for a, b in idle]), idle):
        by_label[label] = by_label.get(label, 0.0) + (b - a) * 1e-9 / len(device_ops)
    gaps = sorted(by_label.items(), key=lambda g: -g[1])
    return Reduction(
        window=(w0, w1), busy_s=busy / len(device_ops), op_s=op_s,
        ops=device_ops, spans=spans, gaps=gaps,
    )


def read_dir(log_dir: str) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_planes(ProfileData.from_file(max(files, key=os.path.getmtime)).planes)


def breakdown(red: Reduction) -> dict:
    """The ``breakdown`` of the result line: the device ops that took most
    time, and the idle time by what the host was doing, each at most
    ``TOP`` entries."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in red.gaps[:TOP]]}
