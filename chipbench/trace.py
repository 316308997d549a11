"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
jitted program and per operation, and idle gaps named by host span.

Only the benchmark's own host spans (``TraceAnnotation`` names starting
with ``SPAN_PREFIX``) and the device planes are read.  The window is the
benchmark's ``chipbench.window`` span: everything is clipped to it.
"""
from __future__ import annotations

import collections
import heapq
import pathlib
import re

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_name(event_name: str) -> str:
    """``jit_serve_step(123)`` -> ``serve_step``."""
    name = _SUFFIX.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _name_gaps(gaps, spans) -> dict:
    """Idle seconds per host span: each gap (start, end) goes to the
    shortest span holding its midpoint (what the host was doing while
    the device waited), or to ``"no span"``.  One sweep over both lists
    sorted by time, with the open spans in a heap by length."""
    out = collections.Counter()
    by_start = sorted(spans, key=lambda s: s[1])
    heap, j = [], 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while j < len(by_start) and by_start[j][1] <= mid:
            n, s, e = by_start[j]
            heapq.heappush(heap, (e - s, e, n))
            j += 1
        while heap and heap[0][1] < mid:       # shortest one has ended;
            heapq.heappop(heap)                # drop it and look again
        # An ended span may still hide under a shorter open one: check.
        live = [h for h in heap if h[1] >= mid]
        if len(live) != len(heap):
            heap = live
            heapq.heapify(heap)
        out[heap[0][2] if heap else "no span"] += (b - a) / 1e9
    return out


def reduce(profile) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``.  Returns seconds:
    ``window_s``, ``busy_s`` (mean over devices), ``programs`` and
    ``ops`` ({name: device seconds}, summed over devices), ``spans``
    (host spans as (name, start_s, end_s) relative to the window start),
    ``module_events`` ((program, start_s, end_s) per device event) and
    ``idle_by_span`` ({span name: idle device seconds in it})."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            lines = {line.name: line for line in plane.lines}
            if MODULES_LINE in lines or OPS_LINE in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    if not devices:
        raise ValueError("trace has no TPU device plane")

    programs = collections.Counter()
    ops = collections.Counter()
    module_events = []
    busy_total = 0.0
    idle = collections.Counter()
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    for lines in devices:
        for ev in getattr(lines.get(MODULES_LINE), "events", ()):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e > s:
                name = program_name(ev.name)
                programs[name] += (e - s) / 1e9
                module_events.append((name, (s - w0) / 1e9, (e - w0) / 1e9))
        busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        ivals = []
        for ev in busy_line.events:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e > s:
                ivals.append((s, e))
                if busy_line is lines.get(OPS_LINE):
                    ops[ev.name] += (e - s) / 1e9
        busy = _union(ivals)
        busy_total += sum(e - s for s, e in busy) / 1e9
        gaps, t = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        for name, secs in _name_gaps(gaps, inner).items():
            idle[name] += secs
    n_dev = len(devices)
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": busy_total / n_dev,
            "devices": n_dev,
            "programs": dict(programs),
            "ops": dict(ops),
            "module_events": module_events,
            "spans": [(n, (a - w0) / 1e9, (b - w0) / 1e9)
                      for n, a, b in spans if n != WINDOW_SPAN],
            "idle_by_span": {k: v / n_dev for k, v in idle.items()}}


def load_and_reduce(trace_dir) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(find_xplane(trace_dir))))
