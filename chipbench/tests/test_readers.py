"""The per-layer readers on a made-up trace: program runs cut by the
window's edges are left out, and the shares follow from the cost
functions and the peaks."""
import pathlib
import types

import pytest

from chipbench import cost, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = harness.load_config(ROOT / "chipbench/configs/yi-9b-24L.json")
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def _data():
    # Three decode steps inside the window, one cut by each edge.
    events = [("serve_step", 0.0, 0.02), ("serve_step", 1.0, 1.02),
              ("serve_step", 2.0, 2.02), ("serve_step", 3.0, 3.02),
              ("serve_step", 9.99, 10.0),
              ("resume_prefill_step", 4.0, 4.1)]
    spans = types.SimpleNamespace(
        decode=[{"t": 0.5, "end": 3.5, "rows": 4, "pos": 1088, "steps": 1}],
        prefill=[{"t": 3.9, "end": 4.2, "rows": 4, "prefix": 1024,
                  "suffix": 64}],
        lookup=[])
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(cfg=CFG), spans=spans, peaks=PEAKS,
        trace={"window_s": 10.0, "module_events": events})


def test_decode_share_counts_whole_runs_only():
    got = harness._reader("decode_hbm_share.online")(_data())
    want = 100 * cost.decode_bytes(CFG, 4, 1088) / (0.02 * 819e9)
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_prefill_and_step_mfu():
    d = _data()
    flops = cost.prefill_flops(CFG, 4, 1024, 64)
    assert harness._reader("prefill_mfu.online")(d) == pytest.approx(
        100 * flops / (0.1 * 197e12))
    step = 3 * cost.decode_flops(CFG, 4, 1088) + flops
    assert harness._reader("step_mfu.online")(d) == pytest.approx(
        100 * step / (10.0 * 197e12))


def test_nothing_to_read_is_none():
    d = _data()
    assert harness._reader("xam_lookup_roofline.online")(d) is None


def test_lookup_roofline_counts_the_searches_work():
    d = _data()
    d.trace["module_events"].append(("xam_search_multiset_pallas", 5.0, 5.0 + 1e-5))
    d.spans.lookup = [{"t": 5.0, "queries": 340, "key_bits": 32, "ways": 512,
                       "sets": 8, "set_bytes": 32 * 512 + 512}]
    ops, nbytes = cost.xam_lookup(340, 32, 512, 8, 32 * 512 + 512)
    assert ops == 2 * 340 * 32 * 512
    assert nbytes == 340 * 32 + 8 * (32 * 512 + 512) + 4 * 340
    least = max(ops / 393e12, nbytes / 819e9)
    assert harness._reader("xam_lookup_roofline.online")(d) == \
        pytest.approx(100 * least / 1e-5)
