"""The program's ``monarch.*`` spans on a made-up profile: device idle goes
to the innermost program span open at the gap, the benchmark's own
reduction is untouched by them, and every reader of them reports nothing
where they are absent."""
import types

import pytest

from chipbench import harness, program_spans, trace

MS = 1_000_000                                   # ns

READERS = ("restore_gb_s.online", "restore_idle_share.batch",
           "decode_gap_us.online", "lookup_wait_ms.batch")


def _ev(name, a, b, **stats):
    return types.SimpleNamespace(name=name, start_ns=a * MS,
                                 duration_ns=(b - a) * MS,
                                 stats=list(stats.items()))


def _line(name, *events):
    return types.SimpleNamespace(name=name, events=list(events))


def _profile(program=True):
    """A 1 s window: a resumed prefill on one worker, four decode steps
    on the other, a lookup before the prefill."""
    worker_a = [_ev("chipbench.prefill", 100, 400),
                _ev("monarch.lookup", 90, 100, queries=136),
                _ev("monarch.lookup.wait", 90, 94),
                _ev("monarch.lookup.search", 94, 100, queries=136),
                _ev("monarch.resume.prefill", 110, 390, rows=2, prefix=1024,
                    suffix=64),
                _ev("monarch.resume.match", 110, 130),
                _ev("monarch.resume.restore", 130, 230, rows=2,
                    nbytes=200_000_000),
                _ev("monarch.resume.step", 230, 250),
                _ev("monarch.resume.slice", 250, 390, nbytes=9)]
    worker_b = [_ev("chipbench.decode", 500, 900),
                _ev("monarch.decode", 500, 900, rows=2, pos=1088, steps=4)]
    for t in (500, 600, 700, 800):
        worker_b += [_ev("monarch.decode.sync", t - 5, t + 8),
                     _ev("monarch.decode.dispatch", t + 8, t + 12)]
    if not program:
        worker_a = [e for e in worker_a if not e.name.startswith("monarch.")]
        worker_b = [e for e in worker_b if not e.name.startswith("monarch.")]
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("worker-a", *worker_a), _line("worker-b", *worker_b),
        _line("tracer", _ev("chipbench.window", 0, 1000))])
    steps = [(510, 600), (610, 700), (710, 800), (810, 900)]
    busy = [(0, 90), (235, 250)] + steps
    modules = ([_ev("jit_other(1)", 0, 90),
                _ev("jit_resume_prefill_step(2)", 235, 250)]
               + [_ev("jit_serve_step(3)", a, b) for a, b in steps])
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Modules", *modules),
        _line("XLA Ops", *[_ev("fusion", a, b) for a, b in busy])])
    return types.SimpleNamespace(planes=[host, device])


def _data(profile):
    data = types.SimpleNamespace(cell=types.SimpleNamespace(name="x"),
                                 trace=trace.reduce(profile))
    data._program_spans = program_spans.from_profile(profile)
    return data


def test_idle_goes_to_the_innermost_program_span():
    ps = program_spans.from_profile(_profile())
    assert ps.idle == pytest.approx({
        "monarch.resume.restore": 0.145,      # 90-235: midpoint in restore
        "monarch.resume.slice": 0.260,        # 250-510: in slice
        "monarch.decode.sync": 0.030,         # the three gaps between steps
        "no span": 0.100})                    # 900-1000


def test_both_views_add_up_to_the_windows_idle():
    prof = _profile()
    bench = trace.reduce(prof)
    ps = program_spans.from_profile(prof)
    assert sum(ps.idle.values()) == pytest.approx(
        bench["window_s"] - bench["busy_s"], rel=1e-12)
    assert bench["idle_by_span"] == pytest.approx({
        "chipbench.prefill": 0.405, "chipbench.decode": 0.030,
        "no span": 0.100})


def test_benchmark_reduction_ignores_program_spans():
    assert trace.reduce(_profile()) == trace.reduce(_profile(program=False))


def test_spans_keep_their_args_and_window_times():
    ps = program_spans.from_profile(_profile())
    (restore,) = ps.named("monarch.resume.restore")
    assert restore.args == {"rows": 2, "nbytes": 200_000_000}
    assert (restore.start, restore.end) == pytest.approx((0.13, 0.23))
    assert len(ps.named("monarch.decode.sync")) == 4


def test_readers():
    d = _data(_profile())
    read = {n: harness._reader(n)(d) for n in READERS}
    assert read == pytest.approx({
        "restore_gb_s.online": 2.0,            # 0.2 GB in 0.1 s
        "restore_idle_share.batch": 14.5,      # 0.145 s of 1 s
        "decode_gap_us.online": 7500.0,        # 30 ms over 4 steps
        "lookup_wait_ms.batch": 4.0})


@pytest.mark.parametrize("name", READERS)
def test_readers_report_nothing_without_program_spans(name):
    d = _data(_profile(program=False))
    assert d._program_spans is None
    assert harness._reader(name)(d) is None


def test_no_trace_file_is_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    d = types.SimpleNamespace(cell=types.SimpleNamespace(name="x"))
    assert program_spans.load(d) is None
    assert all(harness._reader(n)(d) is None for n in READERS)
