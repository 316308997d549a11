"""Helpers the per-layer metric readers share."""
from __future__ import annotations

import json
import pathlib
import statistics

PROGRAMS = pathlib.Path(__file__).resolve().parent / "programs.json"


def program_runs(data, role: str) -> tuple[int, float]:
    """(runs, device seconds) of the programs ``programs.json`` gives
    ``role``, counting only runs wholly inside the traced window."""
    names = {n for n, r in json.loads(PROGRAMS.read_text()).items()
             if r == role}
    w = data.trace["window_s"]
    runs = [(s, e) for n, s, e in data.trace["module_events"]
            if n in names and 0 < s and e < w]
    return len(runs), sum(e - s for s, e in runs)


def share(data, role: str, per_run: list, peak: float) -> float | None:
    """Work a program run needs (the mean over the calls the spans
    recorded; one call or one decode step is one run) times its runs in
    the window, over their device time at ``peak``, in %.  Calls are
    long next to the window (a decode call lasts over a second), so
    counting runs, not the calls the window cut, keeps the edges out."""
    runs, secs = program_runs(data, role)
    if not runs or not per_run:
        return None
    return 100.0 * runs * statistics.fmean(per_run) / (secs * peak)
