"""Differential step-trace pins: the index against its reference.

``tests/kv_index_reference.py`` holds the reference: the index at
``n_shards=1`` admitting by a sequential scan of the candidates in batch
order, looking up through the one-partition kernel over the global
planes.  This module replays the same randomized schedule through the
index under test, at ``n_shards in {1, 2, 4}``, and through the
reference side by side, pinning planes / hits / shadow maps /
replacement counters / wear IDENTICAL after EVERY op — not just at end
of schedule, so a transient divergence (e.g. a boundary set landing on
the wrong partition mid-remap) cannot cancel out before the final check.

On a one-device host every shard count collapses to the unsharded
layout, so the differential pins the round-grid admission against the
sequential scan; under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
multi-device leg) the same tests drive the real ``shard_map`` lookup and
admission, multi-device placement and the ppermute rotation.

Also here: the no-host-transfer admission and rotation pins
(``jax.transfer_guard("disallow")``) and the jit-cache growth cap of the
stacked layout (pow2 Qmax bucketing).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.data.pipeline import fingerprint_blocks
from repro.kernels.xam_search import ops as xam_ops
from repro.serve.kv_index import (CHUNK_TOKENS, KVIndexConfig,
                                  MonarchKVIndex)
from kv_index_reference import ReferenceKVIndex

SHARD_COUNTS = (1, 2, 4)


def _pair(n_shards: int, **kw):
    base = dict(n_sets=8, set_ways=8, admit_after_reads=1, m_writes=2,
                window_ops=256, rotate_every=1 << 30)
    base.update(kw)
    cfg = KVIndexConfig(n_shards=n_shards, **base)
    return MonarchKVIndex(cfg), ReferenceKVIndex(cfg)


def _device_ways(idx: MonarchKVIndex, fps: np.ndarray) -> np.ndarray:
    """The index's own device search for ``fps``: the stacked shard_map
    search on a set mesh, the one-partition kernel otherwise."""
    key_bits = xam_ops.words_to_bits_np(fps, idx.cfg.key_bits)
    return xam_ops.xam_search_multiset_stacked(
        key_bits, idx._set_of(fps), idx.bits, idx.valid,
        mesh=idx.set_mesh)


def _state(idx: MonarchKVIndex) -> dict:
    return dict(
        slot_of=dict(idx.slot_of),
        first_touch=dict(idx.first_touch),
        offset=idx.offset,
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


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_step_trace(seed, n_shards):
    """Randomized admit/lookup/rotate schedule; the index and the
    reference must agree on EVERY intermediate state and every lookup
    result."""
    rng = np.random.default_rng(seed)
    auto, ref = _pair(n_shards)
    rotated = False
    for step in range(10):
        toks = rng.integers(1, 600, (2, 6 * CHUNK_TOKENS)).astype(np.int32)
        op = rng.random()
        if op < 0.55:
            fps = np.unique(
                fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
            auto.admit_fps(fps)
            ref.admit_fps(fps)
            if op < 0.35:      # re-offer crosses the no-allocate gate
                auto.admit_fps(fps)
                ref.admit_fps(fps)
        elif op < 0.85:
            np.testing.assert_array_equal(auto.lookup(toks),
                                          ref.lookup(toks))
        else:
            auto._rotate()
            ref._rotate()
            rotated = True
        _assert_same(_state(auto), _state(ref),
                     f"seed={seed} step={step} n_shards={n_shards}")
        assert auto.wear_report() == ref.wear_report(), (seed, step)
    if not rotated:            # every trace must cross a remap at least once
        auto._rotate()
        ref._rotate()
        _assert_same(_state(auto), _state(ref), f"seed={seed} final rotate")
    assert auto.stats.admissions > 0


@pytest.mark.parametrize("n_shards", [2, 4])
def test_differential_boundary_straddle_after_rotation(n_shards):
    """Fingerprints whose sets sit at shard edges, pushed ACROSS the
    boundary by repeated set+7 rotations: residency must survive the
    remap, and the index and the reference must agree bit-for-bit — the
    exact traffic the ppermute boundary exchange carries."""
    auto, ref = _pair(n_shards, admit_after_reads=0, set_ways=16)
    n_sets = auto.cfg.n_sets
    s_part = n_sets // n_shards
    # enough distinct fps that every set — in particular every shard-edge
    # set (local row 0 and s_part-1 of each shard) — holds residents
    fps = np.arange(1, 257, dtype=np.uint32)
    auto.admit_fps(fps)
    ref.admit_fps(fps)
    edge_sets = {b % n_sets
                 for k in range(n_shards)
                 for b in (k * s_part, (k + 1) * s_part - 1)}
    assert {int(s) for s, _ in auto.slot_of.values()} >= edge_sets
    for rot in range(3):       # offset walks 7, 14, 21 (mod 8: 7, 6, 5)
        auto._rotate()
        ref._rotate()
        _assert_same(_state(auto), _state(ref),
                     f"n_shards={n_shards} rot={rot}")
        hits = auto._shadow_hits(fps)
        # every installed fp must still be found by the DEVICE search
        ways = _device_ways(auto, fps)
        np.testing.assert_array_equal(ways >= 0, hits)
        ways_ref = xam_ops.xam_search_multiset(
            xam_ops.words_to_bits_np(fps, ref.cfg.key_bits),
            ref._set_of(fps), ref.bits, ref.valid)
        np.testing.assert_array_equal(ways, ways_ref)
    assert auto.stats.rotations == 3


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_admission_is_single_dispatch_at_every_shard_count(n_shards):
    """Acceptance pin: one admit_fps batch is ONE device dispatch no
    matter how many shards (and partitions) the index spans."""
    auto, ref = _pair(n_shards, admit_after_reads=0)
    rng = np.random.default_rng(0)
    for _ in range(4):
        fps = np.unique(rng.integers(1, 3000, 24).astype(np.uint32))
        before = xam_ops.ADMIT_LAUNCH_COUNT
        auto.admit_fps(fps)
        assert xam_ops.ADMIT_LAUNCH_COUNT == before + 1
        ref.admit_fps(fps)
    assert auto.stats.admit_calls == 4
    _assert_same(_state(auto), _state(ref), f"n_shards={n_shards}")


def test_admission_moves_no_plane_data_through_host():
    """Acceptance pin: the stacked admission path performs NO implicit
    host transfer — candidate grids enter via explicit device_put, the
    wear knobs were replicated at construction, and only the decision
    grids come back (one explicit device_get per batch).  A scratch index
    with the identical config compiles the exact R/K bucket shapes first,
    so the guarded run measures steady-state dispatch, not tracing."""
    fps = np.arange(1, 49, dtype=np.uint32)
    warm, _ = _pair(4, admit_after_reads=0)
    warm.admit_fps(fps)                   # same cfg + batch -> same shapes
    idx, _ = _pair(4, admit_after_reads=0)
    with jax.transfer_guard("disallow"):
        idx.admit_fps(fps)
    # bit-identical to the unguarded twin, installs included
    _assert_same(_state(idx), _state(warm), "guarded admission")
    assert idx.stats.admissions > 0
    # residents = installs minus the same-batch evictions (table was empty)
    assert (idx._shadow_hits(fps).sum()
            == idx.stats.admissions - idx.stats.evictions)


def test_rotation_moves_no_plane_data_through_host():
    """Acceptance pin: the rotate path performs NO host transfer of plane
    data (device_get/device_put both trip the guard).  Runs on every
    device count — one partition exercises the donated local roll,
    several the ppermute boundary exchange."""
    idx, _ = _pair(4, admit_after_reads=0)
    idx.admit_fps(np.arange(1, 65, dtype=np.uint32))
    with jax.transfer_guard("disallow"):
        idx._rotate()
        idx._rotate()
    assert idx.stats.rotations == 2
    # ...and the remap preserved residency (device search vs the host
    # shadow oracle, outside the guard)
    probe = np.arange(1, 65, dtype=np.uint32)
    ways = _device_ways(idx, probe)
    want = idx._shadow_hits(probe)
    assert want.any()
    np.testing.assert_array_equal(ways >= 0, want)


def test_device_rotation_never_replaces_planes_from_host(monkeypatch):
    """Behavioral twin of the transfer-guard pin (the CPU backend's guard
    cannot see host<->device copies — everything is host memory): the
    rotate path must never place plane data from the host
    (``jax.device_put``) nor fetch it (``jax.device_get``), and the planes
    keep their placement across the remap."""
    idx, ref = _pair(4, admit_after_reads=0)
    fps = np.arange(1, 65, dtype=np.uint32)
    idx.admit_fps(fps)
    ref.admit_fps(fps)
    placed = [x.sharding for x in (idx.bits, idx.valid, idx.fp_of,
                                   idx.read_after)]

    calls = []
    for name in ("device_put", "device_get"):
        orig = getattr(jax, name)
        monkeypatch.setattr(
            jax, name,
            lambda *a, _o=orig, _n=name, **k: (calls.append(_n),
                                               _o(*a, **k))[-1])
    idx._rotate()
    monkeypatch.undo()
    assert calls == []
    assert [x.sharding for x in (idx.bits, idx.valid, idx.fp_of,
                                 idx.read_after)] == placed
    ref._rotate()
    _assert_same(_state(idx), _state(ref), "post-instrumented rotate")


def test_stacked_layout_caps_jit_cache_growth():
    """Satellite pin: DISTINCT ragged batch sizes may not each compile a
    new program — the stacked grouping buckets Qmax to a pow2, so the
    number of distinct padded shapes (== jit cache entries of the fused
    kernel) is logarithmic in the batch-size range."""
    qs = list(range(1, 120, 7))
    shapes = set()
    for q in qs:
        sets = np.arange(q) % 8
        _, _, block_sets, _, padded_q = (
            xam_ops.group_queries_by_set_stacked(sets, 8, 2))
        shapes.add((padded_q, block_sets.shape))
    # 17 ragged sizes -> a handful of pow2 buckets
    assert len(shapes) <= 4, shapes

    # and the end-to-end index path compiles once per bucket, not per size
    jax.clear_caches()
    idx, _ = _pair(1, admit_after_reads=0, n_sets=8)
    rng = np.random.default_rng(0)
    for q in range(1, 14):
        idx.lookup(rng.integers(1, 10_000,
                                (1, q * CHUNK_TOKENS)).astype(np.int32))
    from repro.kernels.xam_search.kernel import xam_search_multiset_pallas
    n_buckets = len({xam_ops.group_queries_by_set(
        np.zeros(q, np.int64), 8)[2] for q in range(1, 14)})
    assert xam_search_multiset_pallas._cache_size() <= n_buckets + 1


# ---------------------------------------------------------------------------
# Packed planes (plane_format="packed8") vs the int8 layout: the SAME
# randomized schedules replayed through both formats must agree on every
# lookup result and every piece of state — with the stored planes compared
# through the unpack (the only field whose raw bytes legitimately differ).
# ---------------------------------------------------------------------------

def _format_pair(n_shards: int, **kw):
    base = dict(n_sets=8, set_ways=8, admit_after_reads=1, m_writes=2,
                window_ops=256, rotate_every=1 << 30)
    base.update(kw)
    return (MonarchKVIndex(KVIndexConfig(
                n_shards=n_shards, plane_format="packed8", **base)),
            MonarchKVIndex(KVIndexConfig(
                n_shards=n_shards, plane_format="int8", **base)))


def _state_unpacked(idx: MonarchKVIndex) -> dict:
    from repro.kernels.common import unpack_bits_np
    s = _state(idx)
    if s["bits"].dtype == np.uint8:
        s["bits"] = unpack_bits_np(s["bits"], idx.cfg.key_bits, axis=1)
    return s


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("seed", [0, 1])
def test_differential_packed_vs_int8_step_trace(seed, n_shards):
    """Randomized admit / re-offer / lookup / rotate schedule through a
    packed8 index and an int8 index side by side: identical hits, shadow
    maps, wear, stats, and (unpacked) stored planes after EVERY op."""
    rng = np.random.default_rng(seed)
    packed, plain = _format_pair(n_shards)
    assert packed.bits.dtype == np.uint8 and plain.bits.dtype == np.int8
    for step in range(10):
        toks = rng.integers(1, 600, (2, 6 * CHUNK_TOKENS)).astype(np.int32)
        op = rng.random()
        if op < 0.55:
            fps = np.unique(
                fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
            packed.admit_fps(fps)
            plain.admit_fps(fps)
            if op < 0.35:
                packed.admit_fps(fps)
                plain.admit_fps(fps)
        elif op < 0.85:
            np.testing.assert_array_equal(packed.lookup(toks),
                                          plain.lookup(toks))
        else:
            packed._rotate()
            plain._rotate()
        _assert_same(_state_unpacked(packed), _state_unpacked(plain),
                     f"seed={seed} step={step} n_shards={n_shards}")
        assert packed.wear_report() == plain.wear_report(), (seed, step)
    assert packed.stats.admissions > 0


@pytest.mark.parametrize("n_shards", [2, 4])
def test_differential_packed_rotation_boundary_exchange(n_shards):
    """Rotation-heavy packed differential: repeated set+7 remaps push
    residents across shard edges; the ppermute boundary exchange carries
    uint8 words instead of int8 bit rows and must land bit-identically."""
    packed, plain = _format_pair(n_shards, admit_after_reads=0, set_ways=16)
    fps = np.arange(1, 257, dtype=np.uint32)
    packed.admit_fps(fps)
    plain.admit_fps(fps)
    for rot in range(3):
        packed._rotate()
        plain._rotate()
        _assert_same(_state_unpacked(packed), _state_unpacked(plain),
                     f"n_shards={n_shards} rot={rot}")
        # every resident must still be found by the packed device search
        ways = _device_ways(packed, fps)
        np.testing.assert_array_equal(
            np.asarray(ways) >= 0, packed._shadow_hits(fps))
    assert packed.stats.rotations == 3


def test_packed_requires_byte_aligned_keys():
    """key_bits not divisible by 8 cannot ride packed planes — the config
    must say so up front, naming the knob."""
    with pytest.raises(ValueError, match="key_bits"):
        MonarchKVIndex(KVIndexConfig(n_sets=8, key_bits=20,
                                     plane_format="packed8"))


def test_packed_install_column_is_fingerprint_bytes():
    """Layout pin: with 32-bit keys a packed stored column IS the
    fingerprint's little-endian bytes (LSB-first packing == LSB-first
    words_to_bits) — the on-disk-obvious identity ARCHITECTURE.md
    documents."""
    idx = MonarchKVIndex(KVIndexConfig(n_sets=8, admit_after_reads=0,
                                       plane_format="packed8"))
    fp = np.uint32(0xDEADBEEF)
    idx.admit_fps(np.asarray([fp], np.uint32))
    (s, w), = [idx.slot_of[int(fp)]]
    col = np.asarray(idx.bits)[s, :, w]
    np.testing.assert_array_equal(
        col, np.frombuffer(np.uint32(fp).tobytes(), np.uint8))


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
