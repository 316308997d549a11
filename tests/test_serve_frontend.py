"""Serving front end: wall-clock t_MWW, the shared request loop, the
launcher's per-batch report, and the serve bench/regression gates.

Wall-clock coverage pins the tentpole contract from three sides:

* CONFIG PLUMBING — ``clock`` validated and threaded through
  ``WearConfig`` / ``KVIndexConfig`` / ``with_lifetime`` (a wall window
  is a real time budget, independent of any op-rate estimate).
* OP-CLOCK BIT-IDENTITY — ``clock="ops"`` (the default) never consults
  the injected wall clock, so every pre-PR schedule is unchanged (the
  existing differential/sharded suites are the behavioral pin; here we
  additionally prove the clock source is untouched).
* WALL SEMANTICS — with a controllable ``now_fn``: window expiry
  unlocks sets as wall time passes, the index still agrees with its
  sequential reference (``tests/kv_index_reference.py``) at n_shards
  {1, 2, 4} (per-batch host-side stamps keep device scans
  deterministic), and the int32 clock rebase is exact (an index driven
  near the rebase boundary matches one driven from zero).
"""
from __future__ import annotations

import os
import sys
import warnings

import numpy as np
import pytest

from repro.core import wear
from repro.launch.serve import RequestRecord, run_request_loop
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import CHUNK_TOKENS, KVIndexConfig, MonarchKVIndex
from kv_index_reference import ReferenceKVIndex

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                      # for `import benchmarks.*`
    sys.path.insert(0, ROOT)


class FakeClock:
    """Injectable ``now_fn``: seconds, advanced explicitly."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# clock plumbing


def test_wear_config_rejects_unknown_clock():
    with pytest.raises(ValueError, match="clock"):
        wear.WearConfig(n_supersets=1, clock="sundial")


def test_kv_index_config_rejects_unknown_clock():
    with pytest.raises(ValueError, match="clock"):
        MonarchKVIndex(KVIndexConfig(n_sets=4, clock="sundial"))


def test_make_config_wall_window_is_a_time_budget():
    ops = wear.make_config(4, clock="ops")
    wall = wear.make_config(4, clock="wall")
    t_mww_s = wear.t_mww_seconds(3, 10.0 * 365.25 * 24 * 3600, 1e8)
    assert ops.t_mww_cycles == int(t_mww_s * wear.CPU_HZ)
    assert wall.t_mww_cycles == int(t_mww_s * wear.WALL_HZ)
    assert wall.clock == "wall"


def test_with_lifetime_wall_window_ignores_op_rate():
    # the wall window depends only on the lifetime math, not on the
    # ops_per_second estimate the op-clock proxy needs
    a = KVIndexConfig.with_lifetime(t_life_years=10.0, clock="wall")
    b = KVIndexConfig.with_lifetime(t_life_years=10.0, clock="wall",
                                    ops_per_second=123.0)
    assert a.window_ops == b.window_ops == 9467280
    assert a.clock == "wall"


def test_ops_clock_never_consults_the_wall_clock():
    """Bit-identity pin for every pre-PR configuration: under the
    default op-counter clock the injected ``now_fn`` is never called, so
    existing schedules cannot observe wall time at all."""
    def boom():
        raise AssertionError("ops clock consulted now_fn")

    cfg = KVIndexConfig(n_sets=8, set_ways=16, admit_after_reads=0)
    with_clock = MonarchKVIndex(cfg, now_fn=boom)
    plain = MonarchKVIndex(cfg)
    rng = np.random.default_rng(2)
    for _ in range(4):
        toks = rng.integers(1, 50_000,
                            (1, 4 * CHUNK_TOKENS)).astype(np.int32)
        with_clock.admit(toks)
        plain.admit(toks)
        np.testing.assert_array_equal(with_clock.lookup(toks),
                                      plain.lookup(toks))
    assert with_clock.slot_of == plain.slot_of
    assert with_clock.wear_report() == plain.wear_report()


# ---------------------------------------------------------------------------
# wall-clock semantics


def _wall_index(clk, *, n_shards: int = 1, window_s: float = 1.0,
                cls=MonarchKVIndex, **kw):
    cfg = dict(n_sets=8, set_ways=4, admit_after_reads=0, m_writes=1,
               window_ops=int(window_s * wear.WALL_HZ), rotate_every=1 << 30,
               clock="wall", n_shards=n_shards)
    cfg.update(kw)
    return cls(KVIndexConfig(**cfg), now_fn=clk)


def test_wall_window_locks_then_expires_with_wall_time():
    """m_writes=1, 1-second window: hammering a tiny index locks sets at
    their budget; the locks must clear as WALL time passes — with no
    further index ops spent — which is exactly what the op-counter proxy
    cannot express."""
    clk = FakeClock()
    idx = _wall_index(clk)
    rng = np.random.default_rng(0)
    fps = np.unique(rng.integers(1, 1 << 30, 256).astype(np.uint32))
    idx.admit_fps(fps)                      # overfill: budgets exhausted
    locked = idx.wear_report()["throttled_sets_now"]
    assert locked > 0
    clk.advance(0.5)                        # still inside the window
    assert idx.wear_report()["throttled_sets_now"] == locked
    clk.advance(1.0)                        # window expired
    assert idx.wear_report()["throttled_sets_now"] == 0
    before = idx.stats.admissions
    idx.admit_fps(np.arange((1 << 31) - 64, 1 << 31, dtype=np.uint32)[:32])
    assert idx.stats.admissions > before    # budget refreshed: admits again


def _state(idx: MonarchKVIndex) -> dict:
    return dict(
        slot_of=dict(idx.slot_of),
        first_touch=dict(idx.first_touch),
        valid=np.asarray(idx.valid).copy(),
        fp_of=np.asarray(idx.fp_of).copy(),
        counter=np.asarray(idx.counter).copy(),
        window_writes=np.asarray(idx.wear_state.window_writes).copy(),
        stats=(idx.stats.admissions, idx.stats.admission_skips,
               idx.stats.throttled, idx.stats.evictions),
    )


def _assert_same(sa: dict, sb: dict, msg: str):
    for key in sa:
        if isinstance(sa[key], np.ndarray):
            np.testing.assert_array_equal(sa[key], sb[key],
                                          err_msg=f"{msg}: {key}")
        else:
            assert sa[key] == sb[key], f"{msg}: {key}"


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_wall_clock_differential_vs_reference(n_shards):
    """The round-grid admission must stay bit-identical to the sequential
    reference under the wall clock: stamps are taken ONCE per admission
    batch on the host, so both see the same cycle values no matter how
    the batch is partitioned."""
    clk = FakeClock()
    auto = _wall_index(clk, n_shards=n_shards, set_ways=8, m_writes=2)
    ref = _wall_index(clk, n_shards=n_shards, set_ways=8, m_writes=2,
                      cls=ReferenceKVIndex)
    rng = np.random.default_rng(11)
    for step in range(8):
        fps = np.unique(rng.integers(1, 1 << 20, 48).astype(np.uint32))
        auto.admit_fps(fps)
        ref.admit_fps(fps)
        probe = rng.integers(1, 1 << 20, (1, 3 * CHUNK_TOKENS)
                             ).astype(np.int32)
        np.testing.assert_array_equal(auto.lookup(probe), ref.lookup(probe))
        _assert_same(_state(auto), _state(ref),
                     f"step={step} n_shards={n_shards} t={clk.t}")
        assert auto.wear_report() == ref.wear_report(), (step, clk.t)
        clk.advance(0.37)               # cross several window boundaries


def test_wall_clock_rebase_is_exact():
    """Driving an index from just under the int32 rebase boundary must
    produce the same planes as driving one from t=0: the window
    arithmetic is difference-based, and the rebase folds the origin
    without disturbing any in-window state."""
    rebase_s = wear.CLOCK_REBASE_AT / wear.WALL_HZ
    near, zero = FakeClock(), FakeClock()
    a = _wall_index(near, set_ways=8, m_writes=2)
    b = _wall_index(zero, set_ways=8, m_writes=2)
    near.t = rebase_s - 0.25            # a starts 0.25 s before the fold
    rng = np.random.default_rng(4)
    for _ in range(6):
        fps = np.unique(rng.integers(1, 1 << 20, 32).astype(np.uint32))
        a.admit_fps(fps)
        b.admit_fps(fps)
        near.advance(0.1)               # crosses CLOCK_REBASE_AT mid-run
        zero.advance(0.1)
    assert a._wall_folded == wear.CLOCK_REBASE_AT
    assert b._wall_folded == 0
    _assert_same(_state(a), _state(b), "rebase")
    assert a.wear_report() == b.wear_report()


# ---------------------------------------------------------------------------
# the shared request loop


def test_request_loop_open_loop_latency_counts_backlog():
    """Open-loop accounting: a request that arrives while the loop is
    busy is charged its queueing delay from the SCHEDULED arrival (the
    anti-coordinated-omission contract), and an idle-arrival request
    pays pure service time."""
    clk = FakeClock()
    idx = MonarchKVIndex(KVIndexConfig(n_sets=4, set_ways=16,
                                       admit_after_reads=0))
    q = AdmitQueue(idx, background=False)
    service_s = 0.1

    def prefill(toks, hits):
        clk.advance(service_s)          # deterministic "compute"

    reqs = [np.arange(1 + 64 * i, 1 + 64 * i + 2 * CHUNK_TOKENS,
                      dtype=np.int32).reshape(1, -1) for i in range(3)]
    recs = run_request_loop(
        q, reqs, prefill_fn=prefill, arrivals_s=[0.0, 0.0, 0.5],
        now_fn=clk, sleep_fn=clk.advance)
    q.close()
    lat = [r.latency_s for r in recs]
    assert lat[0] == pytest.approx(service_s)            # served on time
    assert lat[1] == pytest.approx(2 * service_s)        # waited behind 0
    assert lat[2] == pytest.approx(service_s)            # idle arrival
    assert recs[2].arrival_s == pytest.approx(0.5)
    assert all(r.admitted and not r.retried and not r.dropped for r in recs)
    assert all(isinstance(r, RequestRecord) for r in recs)


class _ScriptedQueue:
    """AdmitQueue stand-in with scripted submit outcomes."""

    def __init__(self, outcomes):
        self._outcomes = list(outcomes)

    def lookup(self, tokens):
        return np.zeros((tokens.shape[0],
                         tokens.shape[1] // CHUNK_TOKENS), bool)

    def submit_tokens(self, tokens):
        return self._outcomes.pop(0)


def test_request_loop_defer_retry_and_drop():
    toks = np.arange(1, 1 + 2 * CHUNK_TOKENS, dtype=np.int32).reshape(1, -1)
    # first submit deferred, retry (after decode) accepted
    recs = run_request_loop(_ScriptedQueue([False, True]), [toks],
                            prefill_fn=lambda t, h: None)
    assert recs[0].retried and recs[0].admitted and not recs[0].dropped
    # both rejected: admission forgone, the request itself still served
    recs = run_request_loop(_ScriptedQueue([False, False]), [toks],
                            prefill_fn=lambda t, h: None)
    assert recs[0].retried and recs[0].dropped and not recs[0].admitted


class _DrainingQueue(_ScriptedQueue):
    """Defer-rejecting queue whose backlog drains at a known FakeClock
    time: ``submit_tokens`` succeeds iff nothing is pending."""

    def __init__(self, clk: FakeClock, drain_at: float):
        super().__init__([])
        self._clk, self._drain_at = clk, drain_at

    def pending(self) -> int:
        return 0 if self._clk.t >= self._drain_at else 3

    def submit_tokens(self, tokens):
        return self.pending() == 0


def test_request_loop_defer_retry_waits_for_drain():
    """The decode-less defer retry must NOT race the still-full queue:
    with a bounded drain-wait it polls ``pending()`` (via the injected
    sleep) until the backlog clears, then the ONE retry lands — no
    over-counted drop.  ``retry_wait_s=0`` restores the immediate
    retry, which loses the race and drops."""
    toks = np.arange(1, 1 + 2 * CHUNK_TOKENS, dtype=np.int32).reshape(1, -1)

    clk = FakeClock()
    q = _DrainingQueue(clk, drain_at=0.02)   # drains within the wait
    recs = run_request_loop(q, [toks], prefill_fn=lambda t, h: None,
                            now_fn=clk, sleep_fn=clk.advance,
                            retry_wait_s=0.1)
    assert recs[0].retried and recs[0].admitted and not recs[0].dropped
    assert clk.t < 0.1 + 1e-9               # stopped as soon as it drained

    clk = FakeClock()
    q = _DrainingQueue(clk, drain_at=0.02)
    recs = run_request_loop(q, [toks], prefill_fn=lambda t, h: None,
                            now_fn=clk, sleep_fn=clk.advance,
                            retry_wait_s=0.0)   # old behavior: no wait
    assert recs[0].retried and recs[0].dropped and not recs[0].admitted


# ---------------------------------------------------------------------------
# launcher report (the empty-slice NaN regression)


@pytest.fixture
def serve_mod(monkeypatch):
    """``repro.launch.serve`` with its compile-cache set-up stubbed out:
    ``main`` would otherwise switch this test process's JAX onto the
    checkout's persistent cache for every later test."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    return serve


def test_serve_main_tiny_prompt_reports_na(capsys, serve_mod):
    """Prefix shorter than one chunk: the per-batch report used to take
    an empty-slice mean (NaN + RuntimeWarning); it must print 'n/a'."""
    serve = serve_mod
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        records = serve.main(
            argv=["--arch", "yi-9b", "--reduced", "--requests", "1",
                  "--batch", "1", "--prompt-len", "16",
                  "--decode-tokens", "2"])
    out = capsys.readouterr().out
    assert "prefix chunks cached n/a" in out
    assert "nan" not in out.lower()
    assert records[0].decoded is not None
    assert records[0].decoded.shape == (1, 2)


def test_serve_main_non_resume_decode_returns_tokens(serve_mod):
    """The non-resume ``model_decode`` used to accumulate greedy tokens
    in ``outs`` and throw them away — the launcher must surface the
    ``(B, decode_tokens)`` array on every record."""
    records = serve_mod.main(
        argv=["--arch", "yi-9b", "--reduced", "--no-resume",
              "--requests", "2", "--batch", "1", "--prompt-len", "32",
              "--decode-tokens", "3"])
    assert len(records) == 2
    for rec in records:
        assert rec.decoded is not None
        assert rec.decoded.shape == (1, 3)
        assert rec.decoded.dtype.kind in "iu"


# ---------------------------------------------------------------------------
# trace replay validation (REPRO_SERVE_TRACE)


def _trace_file(tmp_path, payload: str):
    p = tmp_path / "trace.json"
    p.write_text(payload)
    return str(p)


def test_trace_replay_rejects_malformed_traces(tmp_path, monkeypatch):
    """A short/unsorted/negative trace used to slip through
    ``_trace_arrivals`` silently and corrupt backlog accounting — every
    malformed shape must die with a one-line actionable message."""
    from benchmarks import serve_bench as sb
    cases = [
        ("{not json", "not valid JSON"),
        ('{"a": 1}', "non-empty flat list"),
        ("[]", "non-empty flat list"),
        ("[[0.0, 0.1]]", "non-empty flat list"),
        ("[0.0, NaN, 0.2]", "non-finite"),
        ("[0.0, -0.5, 0.2]", "negative arrival offset"),
        ("[0.0, 0.0, 0.0]", "zero makespan"),    # short + nothing to tile
    ]
    for payload, msg in cases:
        path = _trace_file(tmp_path, payload)
        monkeypatch.setenv("REPRO_SERVE_TRACE", path)
        with pytest.raises(ValueError, match=msg):
            sb._trace_arrivals(6)
        assert path in str(pytest.raises(
            ValueError, sb._trace_arrivals, 6).value)   # names the file


def test_trace_replay_sorts_and_tiles(tmp_path, monkeypatch):
    from benchmarks import serve_bench as sb
    # unsorted -> sorted (replay needs nondecreasing arrivals)
    monkeypatch.setenv("REPRO_SERVE_TRACE",
                       _trace_file(tmp_path, "[0.3, 0.0, 0.1]"))
    arr = sb._trace_arrivals(3)
    np.testing.assert_allclose(arr, [0.0, 0.1, 0.3])
    # short trace -> tiled periodically, still nondecreasing, exactly n
    monkeypatch.setenv("REPRO_SERVE_TRACE",
                       _trace_file(tmp_path, "[0.0, 0.1, 0.2]"))
    arr = sb._trace_arrivals(8)
    assert arr.shape == (8,)
    assert np.all(np.diff(arr) >= 0)
    np.testing.assert_allclose(arr[:3], [0.0, 0.1, 0.2])
    assert arr[3] > 0.2                     # repeats shift past makespan
    # exact-length trace passes through untouched
    monkeypatch.setenv("REPRO_SERVE_TRACE",
                       _trace_file(tmp_path, "[0.0, 0.05, 0.1]"))
    np.testing.assert_allclose(sb._trace_arrivals(3), [0.0, 0.05, 0.1])


# ---------------------------------------------------------------------------
# regression-gate behavior (check_regression + serve artifact)


def test_check_regression_missing_current_is_actionable(tmp_path, capsys):
    from benchmarks import check_regression as cr
    rc = cr.main(["--current", str(tmp_path / "BENCH_kernels.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[perf-smoke] ERROR" in out
    assert "artifact not found" in out
    assert "benchmarks.run" in out          # tells the operator what to run
    assert "Traceback" not in out


def _serve_leg(rate, **kw):
    leg = dict(offered_rps=rate, n_requests=32, p50_ms=3.0, p99_ms=9.0,
               mean_ms=4.0, goodput_rps=rate * 0.9, shed_rate=0.0,
               hit_rate=0.5)
    leg.update(kw)
    return leg


def _http_leg(**kw):
    return _serve_leg(120.0, **{"transport_overhead_ms": 0.8, **kw})


def test_serve_structural_gate():
    from benchmarks import check_regression as cr
    good = {"poisson": [_serve_leg(50.0), _serve_leg(400.0)],
            "http": _http_leg()}
    assert cr.serve_structural_gate(good) == []
    assert cr.serve_structural_gate({"poisson": [_serve_leg(50.0)]})
    assert cr.serve_structural_gate({})
    missing = dict(good, poisson=[_serve_leg(50.0),
                                  {k: v for k, v in _serve_leg(400.0).items()
                                   if k != "p99_ms"}])
    assert any("p99_ms" in line for line in cr.serve_structural_gate(missing))
    bad_frac = dict(good, poisson=[_serve_leg(50.0),
                                   _serve_leg(400.0, shed_rate=1.5)])
    assert any("shed_rate" in line
               for line in cr.serve_structural_gate(bad_frac))
    same_rate = dict(good, poisson=[_serve_leg(50.0), _serve_leg(50.0)])
    assert any("distinct" in line
               for line in cr.serve_structural_gate(same_rate))
    inverted = dict(good, poisson=[_serve_leg(50.0),
                                   _serve_leg(400.0, p50_ms=20.0,
                                              p99_ms=5.0)])
    assert any("p50" in line for line in cr.serve_structural_gate(inverted))


def test_serve_structural_gate_requires_http_leg():
    """The socket path must actually have been driven: a serve artifact
    without the HTTP leg (or with an impossible transport tax) fails
    the always-fatal structural gate."""
    from benchmarks import check_regression as cr
    poisson = [_serve_leg(50.0), _serve_leg(400.0)]
    no_http = {"poisson": poisson}
    assert any("socket path was not driven" in line
               for line in cr.serve_structural_gate(no_http))
    no_overhead = {"poisson": poisson,
                   "http": {k: v for k, v in _http_leg().items()
                            if k != "transport_overhead_ms"}}
    assert any("transport_overhead_ms" in line
               for line in cr.serve_structural_gate(no_overhead))
    negative = {"poisson": poisson,
                "http": _http_leg(transport_overhead_ms=-0.2)}
    assert any("undercut" in line
               for line in cr.serve_structural_gate(negative))
    assert cr.serve_structural_gate({"poisson": poisson,
                                     "http": _http_leg()}) == []


def test_serve_latency_keys_for_timing_compare():
    from benchmarks import check_regression as cr
    doc = {"poisson": [_serve_leg(50.0), _serve_leg(400.0)]}
    keys = cr.serve_latencies(doc)
    assert keys["serve.50rps.p50"] == pytest.approx(3000.0)   # ms -> us
    assert keys["serve.400rps.p99"] == pytest.approx(9000.0)
    assert len(keys) == 4


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
