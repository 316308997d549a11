"""A whole run at a tiny size on the CPU (the look for a chip skipped),
sound and with the timed path broken underneath: ``correct`` must hold
for the sound run and fail for each fault the served cells can have."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import harness, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "tiny"


def _cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny", spec={"name": "tiny", "chips": 1},
        cfg=harness.load_config(TINY / "tiny-yi.json"),
        mix=traffic.load(TINY / "tiny-shared.json"),
        limits=json.loads((TINY / "tiny-limits.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"]
                    if m["name"] in ("req_p50_ms", "setup_s")],
        per_layer=[])


def _alter_token(frontend, admit_q):
    """The first answered token of every request is replaced where the
    decode produces it."""
    router = frontend.router
    decode = router.decode_fn

    def broken(toks, state):
        out = np.array(decode(toks, state))
        out[:, 0] = (out[:, 0] + 1) % 512
        return out
    router.decode_fn = broken


def _zero_slabs(frontend, admit_q):
    """Restored KV slabs come back as zeros."""
    store = admit_q.index.slab_store
    get = store.get

    def broken(fp):
        slab = get(fp)
        if slab is None:
            return None
        import jax
        return jax.tree.map(np.zeros_like, slab)
    store.get = broken


def _run(tamper):
    return harness.run(_cell(), seed=2 ** 33 + 17, seconds=2.0, trace=False,
                       require_chip=False, tamper=tamper)


def test_sound_run_is_correct():
    r = _run(None)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 20 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [_alter_token, _zero_slabs])
def test_fault_is_caught(fault):
    r = _run(fault)
    assert not r["correct"], r["checks"]
    assert any(r["checks"][n]["value"] > r["checks"][n]["limit"]
               for n in harness.GAP_STATS), r["checks"]
