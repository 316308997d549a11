"""Set-axis sharding + async admission coverage.

The load-bearing pin: a randomized lookup/admit/rotate schedule replayed
at ``n_shards in {1, 2, 4}`` must produce IDENTICAL hits, installs
(shadow map + device planes), per-set replacement counters and wear
reports — sharding is a relabeling of who stores a set, never a policy
change.  The index stores each plane as one array sharded over MESH
PARTITIONS (``idx.n_parts``): on a one-device host every shard count
collapses to the exact unsharded path, and under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
multi-device leg) the same matrix exercises the real ``shard_map``
lookup and admission and the ``ppermute`` rotation.  The step-for-step
pins against the sequential reference (``tests/kv_index_reference.py``)
live in ``tests/test_kv_index_differential.py``.

The AdmitQueue tests pin the async relaxation: flush == the same
``admit_fps`` calls inline, rotation is a drain barrier, and
read-your-writes lookups never miss a pending install (concurrency
stress lives in ``tests/test_admit_queue_stress.py``).
"""
from __future__ import annotations

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean checkout: deterministic-cases fallback
    from _propcheck import given, settings, strategies as st

from repro.core import geometry
from repro.data.pipeline import fingerprint_blocks
from repro.launch import mesh as mesh_mod
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import CHUNK_TOKENS, KVIndexConfig, MonarchKVIndex

SHARD_COUNTS = (1, 2, 4)


def _mk(n_shards: int, **kw) -> MonarchKVIndex:
    base = dict(n_sets=8, set_ways=8, admit_after_reads=1, m_writes=2,
                window_ops=256, rotate_every=1 << 30)
    base.update(kw)
    return MonarchKVIndex(KVIndexConfig(n_shards=n_shards, **base))


def _global_state(idx: MonarchKVIndex) -> dict:
    return dict(
        slot_of=dict(idx.slot_of),
        first_touch=dict(idx.first_touch),
        bits=np.asarray(idx.bits).copy(),
        valid=np.asarray(idx.valid).copy(),
        fp_of=np.asarray(idx.fp_of).copy(),
        read_after=np.asarray(idx.read_after).copy(),
        counter=np.asarray(idx.counter).copy(),
        writes=idx.write_distribution(),
        window_writes=np.asarray(idx.wear_state.window_writes).copy(),
        ops=idx.ops_total,
        stats=(idx.stats.admissions, idx.stats.admission_skips,
               idx.stats.throttled, idx.stats.evictions,
               idx.stats.chunk_hits, idx.stats.chunk_misses,
               idx.stats.rotations),
    )


def _assert_same(sa: dict, sb: dict, msg: str):
    for key in sa:
        if isinstance(sa[key], np.ndarray):
            np.testing.assert_array_equal(sa[key], sb[key],
                                          err_msg=f"{msg}: {key}")
        else:
            assert sa[key] == sb[key], (msg, key, sa[key], sb[key])


# ---------------------------------------------------------------------------
# Shard-count invariance: the tentpole pin.
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2 ** 31))
def test_shard_count_invariance(seed):
    """Randomized admit/lookup/rotate schedules (driving installs,
    evictions, no-allocate skips AND t_MWW throttles — asserted below)
    replayed at every shard count produce identical hits, installs, and
    wear reports."""
    rng = np.random.default_rng(seed)
    idxs = [_mk(n) for n in SHARD_COUNTS]
    for step in range(8):
        toks = rng.integers(1, 600, (2, 6 * CHUNK_TOKENS)).astype(np.int32)
        # The first step always offers twice, so every schedule skips
        # (first touch) and installs (second touch) at least once.
        op = 0.0 if step == 0 else rng.random()
        if op < 0.6:
            fps = np.unique(
                fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
            for idx in idxs:
                idx.admit_fps(fps)
            if op < 0.4:          # re-offer: crosses the no-allocate gate
                for idx in idxs:
                    idx.admit_fps(fps)
        elif op < 0.9:
            hits = [idx.lookup(toks) for idx in idxs]
            for h in hits[1:]:
                np.testing.assert_array_equal(hits[0], h)
        else:
            for idx in idxs:
                idx._rotate()
        ref = _global_state(idxs[0])
        for n, idx in zip(SHARD_COUNTS[1:], idxs[1:]):
            _assert_same(ref, _global_state(idx),
                         f"seed={seed} step={step} n_shards={n}")
        reports = [idx.wear_report() for idx in idxs]
        for n, rep in zip(SHARD_COUNTS[1:], reports[1:]):
            assert rep == reports[0], (seed, step, n)
    # The schedule must actually exercise the interesting paths.
    s = idxs[0].stats
    assert s.admissions > 0 and s.admission_skips > 0


def test_shard_invariance_under_eviction_and_throttle_pressure():
    """Deterministic heavy trace: tiny sets force evictions, a tight
    window forces throttles, and explicit rotations force the cross-shard
    remap — all shard counts stay in lockstep."""
    idxs = [_mk(n, set_ways=4, admit_after_reads=0, m_writes=1,
                window_ops=64) for n in SHARD_COUNTS]
    fps = np.arange(1, 129, dtype=np.uint32)
    for chunk in fps.reshape(8, 16):
        for idx in idxs:
            idx.admit_fps(chunk)
        for idx in idxs:      # rotation interleaved with admission
            idx._rotate()
        ref = _global_state(idxs[0])
        for idx in idxs[1:]:
            _assert_same(ref, _global_state(idx), "heavy trace")
    s = idxs[0].stats
    assert s.evictions > 0 and s.throttled > 0 and s.rotations == 8


def test_sharded_state_shapes_and_ownership():
    """Each plane and counter is ONE array over every set, stored in one
    block per MESH PARTITION: n_parts == the ("sets",) mesh size (1 on a
    one-device host — co-located shards collapse to the unsharded
    layout), and partition k's block holds global sets
    ``[k * s_loc, (k + 1) * s_loc)`` on mesh device k."""
    idx = _mk(4, n_sets=8)
    assert idx.sets_per_shard == 2           # logical shard geometry
    want_parts = mesh_mod.set_partitions(4)  # largest divisor host holds
    assert idx.n_parts == want_parts
    s_loc = 8 // want_parts
    devices = (list(idx.set_mesh.devices.flat) if idx.set_mesh is not None
               else [None])
    arrays = {"bits": (idx.bits, (idx.cfg.key_bits, idx.cfg.set_ways)),
              "valid": (idx.valid, (idx.cfg.set_ways,)),
              "counter": (idx.counter, ()),
              "window_writes": (idx.wear_state.window_writes, ())}
    for name, (arr, tail) in arrays.items():
        assert arr.shape == (8,) + tail, name
        blocks = sorted(arr.addressable_shards,
                        key=lambda sh: sh.index[0].start or 0)
        assert len(blocks) == want_parts, name
        for k, sh in enumerate(blocks):
            assert sh.data.shape == (s_loc,) + tail, name
            assert (sh.index[0].start or 0) == k * s_loc, name
            if devices[k] is not None:
                assert sh.device == devices[k], name
    # the global wear view sums the per-partition scalar counters
    assert idx.wear_state.write_counter.shape == ()
    shard, local = geometry.shard_of_set(np.arange(8), 8, 4)
    np.testing.assert_array_equal(shard, np.arange(8) // 2)
    np.testing.assert_array_equal(local, np.arange(8) % 2)


def test_shard_count_must_divide_sets():
    with pytest.raises(ValueError):
        MonarchKVIndex(KVIndexConfig(n_sets=8, n_shards=3))


def test_lookup_is_single_dispatch_at_every_shard_count(rng):
    """The tentpole acceptance pin: ONE fused-search device dispatch per
    lookup batch REGARDLESS of n_shards (the stacked shard_map path on a
    multi-device mesh, the collapsed unsharded launch otherwise), counted
    at the ops layer — where every host-side launch site increments
    ``xam_ops.LAUNCH_COUNT`` exactly once."""
    from repro.kernels.xam_search import ops as xam_ops
    toks = rng.integers(1, 50_000, (4, 256)).astype(np.int32)
    for n_shards in SHARD_COUNTS:
        idx = _mk(n_shards, n_sets=8, admit_after_reads=0)
        before = xam_ops.LAUNCH_COUNT
        s_before = idx.stats.searches
        idx.lookup(toks)       # 64 chunks spread over all sets
        assert xam_ops.LAUNCH_COUNT == before + 1, n_shards
        assert idx.stats.searches == s_before + 1


def test_set_mesh_single_device_is_none():
    """On a 1-device host the ("sets",) mesh degenerates: shards
    co-locate and the index keeps plain single-device arrays (the dry-run
    env is the multi-device path; tests must see the real device
    count)."""
    import jax
    if len(jax.devices()) == 1:
        assert mesh_mod.make_set_mesh(4) is None
    else:
        mesh = mesh_mod.make_set_mesh(4)
        assert mesh is not None and mesh.axis_names == ("sets",)
        assert mesh.devices.size == mesh_mod.set_partitions(4)


# ---------------------------------------------------------------------------
# Async admission queue.
# ---------------------------------------------------------------------------

def _same_index_state(a: MonarchKVIndex, b: MonarchKVIndex):
    assert a.slot_of == b.slot_of
    assert a.first_touch == b.first_touch
    np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))
    np.testing.assert_array_equal(np.asarray(a.fp_of), np.asarray(b.fp_of))
    np.testing.assert_array_equal(a.write_distribution(),
                                  b.write_distribution())
    assert a.stats.admissions == b.stats.admissions
    assert a.stats.admission_skips == b.stats.admission_skips


@pytest.mark.parametrize("background", [False, True])
@pytest.mark.parametrize("n_shards", [1, 2])
def test_queue_flush_matches_inline_admission(rng, background, n_shards):
    """submit*; flush == the same admit_fps calls inline: same shadow
    map, planes, install counts — order is preserved and batches merge
    only while mutually disjoint (touch-count semantics), which keeps
    the drained state bit-identical."""
    cfg = dict(n_sets=4, set_ways=16, admit_after_reads=1, m_writes=1 << 20,
               window_ops=1 << 30)
    inline = MonarchKVIndex(KVIndexConfig(n_shards=n_shards, **cfg))
    queued = MonarchKVIndex(KVIndexConfig(n_shards=n_shards, **cfg))
    q = AdmitQueue(queued, background=background)
    batches = [np.unique(rng.integers(1, 400, 24).astype(np.uint32))
               for _ in range(6)]
    batches += batches[:3]     # re-offers: exercises the touch counter
    for fps in batches:
        inline.admit_fps(fps)
        q.submit(fps)
    q.flush()
    _same_index_state(inline, queued)
    assert q.stats.batches == len(batches)
    q.close()


def test_queue_read_your_writes_flushes_pending(rng):
    import time
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=4, set_ways=32, admit_after_reads=0))
    q = AdmitQueue(idx, background=True, read_your_writes=True)
    # Slow the drain so the lookup deterministically observes the batch
    # as pending (otherwise worker vs main is a scheduling race).
    real_admit = idx.admit_fps
    idx.admit_fps = lambda fps: (time.sleep(0.5), real_admit(fps))[-1]
    toks = rng.integers(1, 1000, (2, 64)).astype(np.int32)
    q.submit_tokens(toks)
    assert q.lookup(toks).all()        # pending installs became visible
    assert q.stats.rww_flushes >= 1
    # an unrelated lookup needs no flush
    other = rng.integers(10_000, 20_000, (1, 32)).astype(np.int32)
    before = q.stats.rww_flushes
    q.lookup(other)
    assert q.stats.rww_flushes == before
    q.close()


def test_queue_rotate_is_drain_barrier(rng):
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=8, set_ways=32, admit_after_reads=0, n_shards=2))
    q = AdmitQueue(idx, background=True, read_your_writes=False)
    toks = rng.integers(1, 4000, (4, 128)).astype(np.int32)
    q.submit_tokens(toks)
    q.rotate()                          # flush-then-remap
    assert q.pending() == 0
    assert idx.stats.rotations == 1
    q.flush()
    with q._idx_lock:
        want = idx._shadow_hits(
            fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
    got = q.lookup(toks).reshape(-1)
    np.testing.assert_array_equal(got, want)
    assert got.all()                    # installs survived the remap
    q.close()


def test_queue_worker_failure_surfaces_on_flush():
    """A failing admission batch must neither kill the drain loop (later
    flushes would hang forever) nor vanish silently: the next barrier
    re-raises, and the queue keeps working afterwards."""
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=4, set_ways=8, admit_after_reads=0))
    q = AdmitQueue(idx, background=True)
    real_admit = idx.admit_fps

    def boom(fps):
        raise ValueError("injected admission failure")

    idx.admit_fps = boom
    q.submit(np.asarray([1, 2, 3], np.uint32))
    with pytest.raises(RuntimeError, match="admission batch failed"):
        q.flush()
    idx.admit_fps = real_admit
    q.submit(np.asarray([4, 5, 6], np.uint32))
    q.flush()                        # worker survived; barrier still works
    assert idx.stats.admissions == 3
    q.close()


def test_queue_close_is_idempotent():
    q = AdmitQueue(MonarchKVIndex(KVIndexConfig(n_sets=4, set_ways=8)))
    q.close()
    q.close()


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
