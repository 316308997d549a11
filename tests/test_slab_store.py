"""KVSlabStore residency: device slabs held within a byte budget, spilled
to host memory beyond it, and the ``device_bytes`` / ``host_bytes`` /
``spilled`` counters through stage, commit, discard, drop and the
index's eviction."""
from __future__ import annotations

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serve.admit_queue import AdmitQueue
from repro.serve.http_frontend import ServeRouter, stats_snapshot
from repro.serve.kv_index import KVIndexConfig, KVSlabStore, MonarchKVIndex

N = 1024                                       # bytes of one test slab


def _slab(i: int):
    """A device slab of N bytes, its values drawn from ``i``."""
    return {"k": jnp.full((128,), i, jnp.float32),
            "v": jnp.full((128,), -i, jnp.float32)}


def _counters(store):
    s = store.stats()
    return s["device_bytes"], s["host_bytes"], s["spilled"]


def test_lifecycle_counters_and_spill():
    store = KVSlabStore(device_budget=2 * N + N // 2)
    for fp in range(1, 6):
        store.stage(fp, _slab(fp))
    assert store.staged_fps() == {1, 2, 3, 4, 5}
    assert _counters(store) == (0, 0, 0)      # staged slabs are not resident
    store.commit(1)
    store.commit(2)
    assert _counters(store) == (2 * N, 0, 0)
    store.commit(3)                           # over the budget: to the host
    assert _counters(store) == (2 * N, N, 1)
    host = store.get(3)
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(host))
    np.testing.assert_array_equal(host["k"], np.full(128, 3, np.float32))
    assert isinstance(store.get(1)["k"], jax.Array)
    store.discard(4)                          # skipped offer: nothing held
    store.commit(4)                           # nothing staged: no-op
    assert store.staged_fps() == {5}
    assert _counters(store) == (2 * N, N, 1)
    assert store.get_many([2, 4, 3]) == [store.get(2), None, host]
    store.drop(1)                             # eviction frees device bytes
    assert _counters(store) == (N, N, 1)
    store.commit(5)                           # fits again
    assert _counters(store) == (2 * N, N, 1)
    store.drop(3)
    store.drop(3)                             # twice: no-op
    assert _counters(store) == (2 * N, 0, 1)
    store.stage(2, _slab(20))                 # re-offered while resident
    store.commit(2)
    assert _counters(store) == (2 * N, 0, 1)
    assert int(store.get(2)["k"][0]) == 20
    assert store.resident_fps() == {2, 5}
    assert store.resident_bytes == 2 * N
    assert store.stats()["resident"] == 2


def test_host_slabs_count_as_host_bytes():
    store = KVSlabStore()
    store.stage(1, {"k": np.zeros(256, np.float32)})
    store.commit(1)
    assert _counters(store) == (0, N, 0)


class _Device:
    """Stands in for the chip: ``memory_stats()`` as a TPU reports it."""

    def __init__(self, limit, peak):
        self.stats = {"bytes_limit": limit, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self.stats


def _with_device(monkeypatch, device):
    monkeypatch.setattr(KVSlabStore, "_device_of",
                        staticmethod(lambda slab: device))


def test_budget_from_memory_stats(monkeypatch):
    """The budget is the limit less a sixteenth, less the largest footprint
    reached apart from resident slabs: a peak that the slabs themselves
    raise leaves it alone, a larger transient shrinks it."""
    dev = _Device(limit=16 * N, peak=10 * N)
    _with_device(monkeypatch, dev)
    store = KVSlabStore()                    # 16 - 1 - 10 = 5 slabs
    for fp in range(1, 7):
        store.stage(fp, _slab(fp))
        store.commit(fp)
    assert _counters(store) == (5 * N, N, 1)
    dev.stats["peak_bytes_in_use"] = 15 * N  # raised by the slabs alone
    store.stage(7, _slab(7))
    store.commit(7)                          # the budget is still 5
    assert _counters(store) == (5 * N, 2 * N, 2)
    store.drop(1)
    store.stage(8, _slab(8))
    store.commit(8)
    assert _counters(store) == (5 * N, 2 * N, 2)
    dev.stats["peak_bytes_in_use"] = 17 * N  # a transient 2 slabs larger
    store.stage(9, _slab(9))
    store.commit(9)                          # the budget is now 3
    assert _counters(store) == (5 * N, 3 * N, 3)
    for fp in (2, 3, 4):
        store.drop(fp)
    store.stage(10, _slab(10))
    store.commit(10)
    assert _counters(store) == (3 * N, 3 * N, 3)
    store.stage(11, _slab(11))
    store.commit(11)
    assert _counters(store) == (3 * N, 4 * N, 4)


def test_unbounded_without_memory_stats(monkeypatch):
    dev = types.SimpleNamespace(memory_stats=lambda: None)
    _with_device(monkeypatch, dev)
    store = KVSlabStore()
    for fp in range(1, 33):
        store.stage(fp, _slab(fp))
        store.commit(fp)
    assert _counters(store) == (32 * N, 0, 0)


@pytest.mark.parametrize("budget", [None, 8 * N])
def test_eviction_frees_device_bytes_in_lockstep(budget):
    """Admissions flooding a small index evict resident fingerprints:
    their slabs leave the store with them, and the byte counters always
    equal the resident slabs'."""
    store = KVSlabStore(device_budget=budget)
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=4, set_ways=4, admit_after_reads=0, rotate_every=1 << 30),
        slab_store=store)
    q = AdmitQueue(idx)
    rng = np.random.default_rng(7)
    try:
        for _ in range(6):
            fps = np.unique(rng.integers(1, 1 << 30, 12).astype(np.uint32))
            for f in fps:
                store.stage(int(f), _slab(int(f) & 0xFF))
            q.submit(fps)
            q.flush()
            audit = idx.slab_lockstep_report()
            assert not audit["missing_slabs"] and not audit["orphan_slabs"]
            dev, host, spilled = _counters(store)
            assert dev + host == N * len(store.resident_fps())
            if budget is not None:
                assert dev <= budget
    finally:
        q.close()
    assert idx.stats.evictions > 0
    assert len(store.resident_fps()) <= 16
    assert (spilled > 0) == (budget is not None)


def test_stats_endpoint_reports_the_store():
    store = KVSlabStore()
    idx = MonarchKVIndex(KVIndexConfig(n_sets=4, set_ways=4),
                         slab_store=store)
    store.stage(1, _slab(1))
    store.commit(1)
    for index, want in ((idx, store.stats()),
                        (MonarchKVIndex(KVIndexConfig(n_sets=4)), None)):
        q = AdmitQueue(index)
        router = ServeRouter(q, prefill_fn=lambda t, h: None, n_workers=1)
        try:
            assert stats_snapshot(router)["slab_store"] == want
        finally:
            router.close()
            q.close()
    assert want is None and store.stats()["device_bytes"] == N
