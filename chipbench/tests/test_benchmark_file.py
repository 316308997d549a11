"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, traffic mix, limits and metric readers by name, and a new
cell is new files and entries, with no edit to a file that is there."""
import collections
import json
import pathlib
import re
import shutil

import pytest

from chipbench import harness, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load(cell):
    c = harness.load_cell(cell, ROOT)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in {e["name"] for e in c.end_to_end}
        assert callable(harness._reader(m["name"]))
    assert traffic.prompt_tokens(c.mix) % 16 == 0
    plan = traffic.build(c.mix, c.cfg["vocab_size"], 2 ** 33 + 1, 10.0)
    assert plan.tokens(0).shape == (c.mix["rows"],
                                    traffic.prompt_tokens(c.mix))


def test_same_seed_same_work_other_seed_same_sizes():
    mix = traffic.load(ROOT / "chipbench/traffic/docqa-shared.json")
    a = traffic.build(mix, 64000, 2 ** 32 + 3, 40.0)
    b = traffic.build(mix, 64000, 2 ** 32 + 3, 40.0)
    c = traffic.build(mix, 64000, 7, 40.0)
    assert (a.arrivals_s == b.arrivals_s).all()
    assert (a.tokens(5) == b.tokens(5)).all()
    # Another seed: the same number of arrivals and the same popularity
    # counts, in another order and over other documents.
    assert len(a) == len(c)
    assert a.arrivals_s[-1] == pytest.approx(c.arrivals_s[-1])
    assert sorted(collections.Counter(a.picks.tolist()).values()) == \
        sorted(collections.Counter(c.picks.tolist()).values())
    assert not (a.arrivals_s == c.arrivals_s).all()


def test_a_new_cell_is_new_files(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "yi9b.docqa-long", "config": "yi-9b-24L",
        "traffic": "docqa-long", "chips": 1, "why": "longer documents"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "yi9b.docqa-shared" in m.get("workloads", []):
            m["workloads"].append("yi9b.docqa-long")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = json.loads(
        (ROOT / "chipbench/traffic/docqa-shared.json").read_text())
    mix["prefix_tokens"] = 2048
    (tmp_path / "chipbench/traffic/docqa-long.json").write_text(
        json.dumps(mix))
    shutil.copy(ROOT / "chipbench/limits/yi9b.docqa-shared.json",
                tmp_path / "chipbench/limits/yi9b.docqa-long.json")
    c = harness.load_cell("yi9b.docqa-long", tmp_path)
    assert traffic.prompt_tokens(c.mix) == 2048 + 64
    assert [m["name"] for m in c.per_layer] == [
        m["name"] for m in harness.load_cell("yi9b.docqa-shared",
                                             ROOT).per_layer]
