"""The serving path's own host spans (``monarch.*``, ``repro/serve/spans.py``)
in a traced run, for the per-layer metrics that read them.

Once per run, cached on the ``RunData``, the run's ``.xplane.pb`` (which
``run.py`` writes under ``.chipbench_trace/<cell>``) is read again.  The
device's idle time is named by ``trace.reduce`` itself, over a view of the
profile in which the program's spans stand in for the benchmark's: each
``monarch.*`` span renamed under ``trace.SPAN_PREFIX``, the window span
kept, the benchmark's other spans dropped.  So a gap goes to the innermost
program span open at its midpoint, by the same arithmetic as the
benchmark's own breakdown, and the named seconds add up to the window's
idle.  A program without these spans (or a run without a trace) gives
``None``, and every reader then reports nothing.
"""
from __future__ import annotations

import dataclasses
import pathlib
import types

from chipbench import trace

PREFIX = "monarch."
TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".chipbench_trace"
_CACHE = "_program_spans"


@dataclasses.dataclass
class Span:
    name: str
    line: int          # one host thread's timeline: nesting is per line
    start: float       # seconds from the window's start
    end: float
    args: dict


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    idle: dict         # {program span name or "no span": idle device s}
    spans: list        # every program span wholly inside the window

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def has(self, name: str) -> bool:
        return any(s.name == name for s in self.spans)


def _event(name, ev):
    return types.SimpleNamespace(name=name, start_ns=ev.start_ns,
                                 duration_ns=ev.duration_ns)


def program_view(profile):
    """``profile`` with the program's spans in place of the benchmark's:
    what ``trace.reduce`` reads (device planes untouched)."""
    planes = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            planes.append(plane)
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    events.append(ev)
                elif ev.name.startswith(PREFIX):
                    events.append(_event(trace.SPAN_PREFIX + ev.name, ev))
            lines.append(types.SimpleNamespace(name=line.name,
                                               events=events))
        planes.append(types.SimpleNamespace(name=plane.name, lines=lines))
    return types.SimpleNamespace(planes=planes)


def from_profile(profile) -> ProgramSpans | None:
    raw, windows, n_line = [], [], 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == trace.WINDOW_SPAN:
                    windows.append((ev.start_ns, end))
                elif ev.name.startswith(PREFIX):
                    raw.append((ev.name, n_line, ev.start_ns, end,
                                dict(ev.stats)))
            n_line += 1
    if not raw or not windows:
        return None
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    reduced = trace.reduce(program_view(profile))
    cut = len(trace.SPAN_PREFIX)
    idle = {(k[cut:] if k.startswith(trace.SPAN_PREFIX) else k): v
            for k, v in reduced["idle_by_span"].items()}
    spans = [Span(n, ln, (s - w0) / 1e9, (e - w0) / 1e9, args)
             for n, ln, s, e, args in raw if w0 <= s and e <= w1]
    return ProgramSpans(window_s=reduced["window_s"], idle=idle,
                        spans=spans)


def load(data) -> ProgramSpans | None:
    """The program spans of ``data``'s traced run (read once)."""
    if not hasattr(data, _CACHE):
        try:
            path = trace.find_xplane(TRACE_DIR / data.cell.name)
        except FileNotFoundError:
            got = None
        else:
            from jax.profiler import ProfileData
            got = from_profile(ProfileData.from_file(str(path)))
        setattr(data, _CACHE, got)
    return getattr(data, _CACHE)

