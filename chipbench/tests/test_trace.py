"""The trace reduction against a small trace recorded on a v5e chip:
two jitted programs run three times each inside ``chipbench.prefill``
and ``chipbench.decode`` spans, within a ``chipbench.window`` span."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(DATA))


def _device_events(profile, line_name):
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == line_name:
                    return [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
    raise AssertionError(line_name)


def _window(profile):
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "chipbench.window":
                        return e.start_ns, e.start_ns + e.duration_ns
    raise AssertionError("no window span")


def test_window_and_programs(profile):
    r = trace.reduce(profile)
    w0, w1 = _window(profile)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    inside = [(s, d) for _, s, d in _device_events(profile, "XLA Modules")
              if w0 <= s and s + d <= w1]
    assert r["programs"] == {"_lambda": pytest.approx(
        sum(d for _, d in inside) / 1e9, rel=1e-9)}
    assert len(r["module_events"]) == len(inside) == 5


def test_busy_and_idle_add_up(profile):
    r = trace.reduce(profile)
    ops = [(s, s + d) for _, s, d in _device_events(profile, "XLA Ops")]
    assert 0 < r["busy_s"] <= sum(e - s for s, e in ops) / 1e9
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert set(r["idle_by_span"]) <= {"chipbench.prefill",
                                      "chipbench.decode", "no span"}


def test_spans_are_read(profile):
    names = [n for n, _, _ in trace.reduce(profile)["spans"]]
    assert names.count("chipbench.prefill") == 3
    assert names.count("chipbench.decode") == 3


def test_program_name():
    assert trace.program_name("jit_serve_step(123)") == "serve_step"
    assert trace.program_name("jit__admit_rounds_body(7)") == \
        "_admit_rounds_body"


def test_gap_naming_picks_the_shortest_open_span():
    spans = [("chipbench.decode", 0, 100), ("chipbench.prefill", 40, 60)]
    gaps = [(45, 55), (10, 20), (150, 160)]
    got = trace._name_gaps(gaps, spans)
    assert got == {"chipbench.prefill": 10e-9, "chipbench.decode": 10e-9,
                   "no span": 10e-9}
