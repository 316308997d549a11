"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, traffic mix, limits and metric readers by name, every
configuration its reference module, and a new cell, also of a new
architecture with a reference of its own, is new files and entries,
with no edit to a file that is there."""
import collections
import hashlib
import json
import pathlib
import re
import shutil

import numpy as np
import pytest

from chipbench import cost, harness, reference, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "tiny"
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


CONFIG_FILES = sorted((ROOT / "chipbench/configs").glob("*.json")) + [
    ROOT / "chipbench/tests/tiny/tiny-yi.json"]


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_configuration_names_a_reference_with_the_interface(path):
    cfg = harness.load_config(path)
    mod = reference.module(cfg)
    assert pathlib.Path(mod.__file__) == (
        ROOT / "chipbench/reference" / f"{cfg['reference']}.py")
    for fn in ("logits", "prefill_flops", "decode_flops", "decode_bytes"):
        assert callable(getattr(mod, fn)), fn


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_a_configuration_without_reference_is_an_error(tmp_path, path):
    cfg = json.loads(path.read_text())
    del cfg["reference"]
    bad = tmp_path / path.name
    bad.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=re.escape(str(bad))):
        harness.load_config(bad)


def _tree_digest() -> dict:
    """Content of every file of the benchmark under the root."""
    files = [ROOT / "BENCHMARK.json"] + [
        p for p in sorted((ROOT / "chipbench").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts]
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


#: A reference of an architecture the benchmark does not have: after
#: token t its logits favour (7 t + 1) mod vocab; its counts are made up.
TOY_REFERENCE = '''
import numpy as np


def logits(cfg, tokens, first, quant=None):
    v = cfg["vocab_size"]
    nxt = (np.asarray(tokens)[:, first:] * 7 + 1) % v
    out = np.zeros(nxt.shape + (v,), np.float32)
    np.put_along_axis(out, nxt[..., None], 1.0, axis=-1)
    return out


def prefill_flops(cfg, rows, prefix, suffix):
    return 11.0 * rows * (prefix + suffix)


def decode_flops(cfg, rows, pos):
    return 13.0 * rows * pos


def decode_bytes(cfg, rows, pos):
    return 17.0 * rows + pos
'''


def _toy_greedy(prompt, n, vocab):
    out, t = [], int(prompt[-1])
    for _ in range(n):
        t = (7 * t + 1) % vocab
        out.append(t)
    return out


def _new_dense_cell(tmp_path, bench):
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


def _new_architecture(tmp_path, bench):
    """A configuration whose ``reference`` names a module of its own:
    the cell loads, its answers are checked against that module's
    logits and its step is counted by that module's counts."""
    tree = tmp_path / "chipbench"
    (tree / "reference/toy_hybrid.py").write_text(TOY_REFERENCE)
    (tree / "configs/toy-hybrid.json").write_text(json.dumps({
        "name": "toy-hybrid", "reference": "toy_hybrid",
        "vocab_size": 512}))
    shutil.copy(TINY / "tiny-shared.json", tree / "traffic/toy-docs.json")
    shutil.copy(TINY / "tiny-limits.json", tree / "limits/toy.docs.json")
    bench["configs"].append({
        "name": "toy-hybrid", "source": "https://example.org/toy",
        "file": "chipbench/configs/toy-hybrid.json", "reduced": [],
        "why": "a hybrid the dense reference cannot compute"})
    bench["workloads"].append({
        "name": "toy.docs", "config": "toy-hybrid", "traffic": "toy-docs",
        "chips": 1, "why": "a new architecture as new files"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "yi9b.docqa-shared" in m.get("workloads", []):
            m["workloads"].append("toy.docs")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.load_cell("toy.docs", tmp_path)
    assert pathlib.Path(reference.module(c.cfg).__file__) == \
        tree / "reference/toy_hybrid.py"
    vocab, n = c.cfg["vocab_size"], c.mix["decode_tokens"]
    plan = traffic.build(c.mix, vocab, 2 ** 33 + 5, 2.0)
    prompts = np.concatenate([plan.tokens(i) for i in range(6)])
    served = np.array([_toy_greedy(p, n, vocab) for p in prompts], np.int32)
    ref = harness.reference_logits(c, prompts, served)
    assert ref.shape == (6, n, vocab)
    assert (ref.argmax(-1) == served).all()

    def answered(alter):
        recs = []
        for i in range(len(plan)):
            toks = [_toy_greedy(r, n, vocab) for r in plan.tokens(i)]
            toks[0][-1] = (toks[0][-1] + alter) % vocab
            recs.append(harness.Record(i=i, due=0.0, sent=0.0, status=200,
                                       doc={"tokens": toks}))
        return recs
    gaps = harness.check_sample(c, plan, answered(0), 2 ** 33 + 5)
    assert gaps.shape == (c.mix["sample"], n) and gaps.max() == 0.0
    assert harness.check_sample(c, plan, answered(1), 2 ** 33 + 5).max() == 1.0

    assert cost.prefill_flops(c.cfg, 2, 64, 16) == 11.0 * 2 * 80
    assert cost.decode_flops(c.cfg, 3, 90) == 13.0 * 3 * 90
    assert cost.decode_bytes(c.cfg, 4, 90) == 17.0 * 4 + 90


@pytest.mark.parametrize("case", [_new_dense_cell, _new_architecture],
                         ids=["dense_config", "own_reference"])
def test_a_new_cell_is_new_files(tmp_path, case):
    before = _tree_digest()
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    case(tmp_path, json.loads(json.dumps(BENCH)))
    assert _tree_digest() == before
