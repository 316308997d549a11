"""MonarchKVIndex coverage: the fused single-launch lookup pinned against
the seed's per-set reference flow, plus the §8 durability policies —
no-allocate admission, t_MWW throttling, cold-victim eviction, rotary
remap — and randomized lookup-vs-shadow-map agreement."""
from __future__ import annotations

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean checkout: deterministic-cases fallback
    from _propcheck import given, settings, strategies as st

import jax.numpy as jnp

from repro.data.pipeline import fingerprint_blocks
from repro.kernels.xam_search.ref import xam_search_ref
from repro.serve.kv_index import CHUNK_TOKENS, KVIndexConfig, MonarchKVIndex


def _small_cfg(**kw) -> KVIndexConfig:
    base = dict(n_sets=4, set_ways=64, admit_after_reads=0,
                m_writes=1 << 20, window_ops=1 << 30)
    base.update(kw)
    return KVIndexConfig(**base)


# ---------------------------------------------------------------------------
# Config hygiene.
# ---------------------------------------------------------------------------

def test_cfg_default_constructed_per_instance():
    a = MonarchKVIndex()
    b = MonarchKVIndex()
    assert a.cfg is not b.cfg          # no shared mutable default
    a.cfg.n_sets = 7
    assert b.cfg.n_sets == KVIndexConfig().n_sets


# ---------------------------------------------------------------------------
# Fused lookup: one launch, bit-identical to the seed's per-set flow.
# ---------------------------------------------------------------------------

def test_lookup_is_single_kernel_launch(rng):
    idx = MonarchKVIndex(_small_cfg(n_sets=8))
    toks = rng.integers(1, 5000, (4, 256)).astype(np.int32)
    idx.admit(toks)
    before = idx.stats.searches
    idx.lookup(toks)                   # 64 chunks spread over all 8 sets
    assert idx.stats.searches == before + 1


def _per_set_reference_lookup(idx: MonarchKVIndex,
                              tokens: np.ndarray) -> np.ndarray:
    """The seed implementation: one xam_search_ref per distinct set with
    host-side validity masking — the bit-identity oracle for lookup()."""
    fps = fingerprint_blocks(tokens, CHUNK_TOKENS)
    flat = fps.reshape(-1)
    sets = idx._set_of(flat)
    hit = np.zeros(flat.shape[0], bool)
    valid = np.asarray(idx.valid)
    bits = np.asarray(idx.bits)
    for s in np.unique(sets):
        sel = sets == s
        keys = ((flat[sel].astype(np.uint32)[:, None]
                 >> np.arange(idx.cfg.key_bits, dtype=np.uint32)) & 1
                ).astype(np.int8)
        m = np.asarray(xam_search_ref(
            jnp.asarray(keys), jnp.asarray(bits[int(s)]),
            jnp.ones_like(jnp.asarray(keys))))
        m = m & valid[int(s)][None, :]
        hit[sel] = m.any(axis=1)
    return hit.reshape(fps.shape)


def test_lookup_bit_identical_to_per_set_reference(rng):
    idx = MonarchKVIndex(_small_cfg(n_sets=8, set_ways=32))
    seen = rng.integers(1, 4000, (4, 128)).astype(np.int32)
    idx.admit(seen)
    mixed = np.concatenate(
        [seen[:2], rng.integers(1, 4000, (3, 128)).astype(np.int32)])
    got = idx.lookup(mixed)
    want = _per_set_reference_lookup(idx, mixed)
    np.testing.assert_array_equal(got, want)
    assert got.any()                   # admitted chunks hit


def test_lookup_empty_and_short_tokens():
    idx = MonarchKVIndex(_small_cfg())
    short = np.ones((2, CHUNK_TOKENS - 1), np.int32)   # 0 whole chunks
    assert idx.lookup(short).shape == (2, 0)


# ---------------------------------------------------------------------------
# Admission policy: no-allocate filter and t_MWW throttle.
# ---------------------------------------------------------------------------

def test_no_allocate_filter_counts_touches(rng):
    idx = MonarchKVIndex(_small_cfg(admit_after_reads=2))
    toks = rng.integers(1, 1000, (1, 64)).astype(np.int32)
    idx.admit(toks)                    # touch 1
    idx.admit(toks)                    # touch 2
    assert idx.stats.admissions == 0
    assert idx.stats.admission_skips > 0
    idx.admit(toks)                    # touch 3: over the R threshold
    assert idx.stats.admissions > 0
    assert idx.lookup(toks).all()


def test_t_mww_throttle_blocks_admissions(rng):
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=1, set_ways=64, admit_after_reads=0, m_writes=0,
        window_ops=1 << 30))
    toks = rng.integers(1, 100_000, (1, 16 * 16)).astype(np.int32)
    idx.admit(toks)
    assert idx.stats.admissions == 0
    assert idx.stats.throttled > 0
    assert not idx.lookup(toks).any()  # recompute-served, never installed


def test_t_mww_window_reset_reopens_admission(rng):
    # budget = set_ways * m_writes = 4 installs per 16-op window (the shared
    # core/wear.py accounting; ops stand in for cycles).
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=1, set_ways=4, admit_after_reads=0, m_writes=1,
        window_ops=16))
    idx.admit_fps(np.arange(1, 9, dtype=np.uint32))
    assert idx.stats.admissions == 4       # budget exhausted mid-batch
    assert idx.stats.throttled == 4
    toks = rng.integers(1, 100_000, (1, 8 * CHUNK_TOKENS)).astype(np.int32)
    idx.lookup(toks)                       # ops advance past the window
    assert idx.ops_total >= 16
    idx.admit_fps(np.arange(100, 103, dtype=np.uint32))
    assert idx.stats.admissions == 7       # window rolled over: admitting again
    assert idx.stats.throttled == 4


# ---------------------------------------------------------------------------
# Eviction: D̄&R̄-style cold victims go first.
# ---------------------------------------------------------------------------

def test_eviction_prefers_never_reread_ways():
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=1, set_ways=8, admit_after_reads=0, m_writes=1 << 20,
        window_ops=1 << 30))
    fps = [np.uint32(f) for f in range(1, 9)]
    for fp in fps:
        idx.admit_fps(np.asarray([fp], np.uint32))
    assert len(idx.slot_of) == 8       # set full
    hot = fps[:5]
    for fp in hot:
        idx.admit_fps(np.asarray([fp], np.uint32))  # marks read_after
    idx.admit_fps(np.asarray([1000], np.uint32))    # forces one eviction
    assert idx.stats.evictions == 1
    for fp in hot:                     # re-read ways were not the victim
        assert int(fp) in idx.slot_of
    assert 1000 in idx.slot_of


def test_eviction_updates_device_state():
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=1, set_ways=4, admit_after_reads=0, m_writes=1 << 20,
        window_ops=1 << 30))
    for f in range(1, 10):             # overflows the 4-way set
        idx.admit_fps(np.asarray([f], np.uint32))
    assert idx.stats.evictions > 0
    # device planes and host shadow stay consistent through evictions
    assert int(np.asarray(idx.valid).sum()) == len(idx.slot_of)
    resident = np.asarray(sorted(idx.slot_of), np.uint32)
    assert idx._shadow_hits(resident).all()
    fp_plane = np.asarray(idx.fp_of)[0]
    for fp, (s, w) in idx.slot_of.items():
        assert fp_plane[w] == fp


# ---------------------------------------------------------------------------
# Rotary remap.
# ---------------------------------------------------------------------------

def test_rotary_remap_moves_new_placements(rng):
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=8, set_ways=64, admit_after_reads=0, m_writes=1 << 20,
        window_ops=1 << 30, rotate_every=16))
    toks = rng.integers(1, 1 << 20, (4, 256)).astype(np.int32)
    idx.admit(toks)
    assert idx.stats.rotations >= 1
    assert idx.offset == (7 * idx.stats.rotations) % idx.cfg.n_sets
    fp = np.uint32(0xDEAD)
    before = idx._set_of(np.asarray([fp]))[0]
    idx._rotate()
    after = idx._set_of(np.asarray([fp]))[0]
    assert after == (before + 7) % idx.cfg.n_sets


# ---------------------------------------------------------------------------
# Batched admission: one device call, sequential-order equivalence.
# ---------------------------------------------------------------------------

def test_admit_is_single_device_call(rng):
    """The whole admission batch goes through ONE jitted device launch (the
    pre-PR implementation issued one install call per fingerprint)."""
    idx = MonarchKVIndex(_small_cfg(n_sets=8))
    toks = rng.integers(1, 50_000, (4, 256)).astype(np.int32)
    idx.admit(toks)                        # 64 unique chunks, 8 sets
    assert idx.stats.admit_calls == 1
    idx.admit(toks)                        # resident re-offers: still 1 call
    assert idx.stats.admit_calls == 2
    assert idx.stats.admissions == 64


def _snapshot(idx: MonarchKVIndex):
    return dict(
        slot_of=dict(idx.slot_of),
        valid=np.asarray(idx.valid).copy(),
        fp_of=np.asarray(idx.fp_of).copy(),
        read_after=np.asarray(idx.read_after).copy(),
        set_writes=np.asarray(idx.set_writes).copy(),
        counter=np.asarray(idx.counter).copy(),   # per-set replacement ctrs
        ops=idx.ops_total,
        window_writes=np.asarray(idx.wear_state.window_writes).copy(),
        locked_until=np.asarray(idx.wear_state.locked_until).copy(),
        stats=(idx.stats.admissions, idx.stats.admission_skips,
               idx.stats.throttled, idx.stats.evictions),
    )


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2 ** 31), n_sets=st.sampled_from([1, 4, 8]))
def test_batched_admit_equals_sequential_order(seed, n_sets):
    """Satellite pin: a randomized admit/lookup/evict/rotate schedule run
    through the batched pipeline must equal the same schedule admitted one
    fingerprint at a time (the seed's sequential admission order) — same
    shadow map, device planes, wear state and stats.  The only intentional
    divergence from the seed is documented in kv_index.py: rotation now
    remaps resident entries instead of orphaning them, and the t_MWW window
    is the shared core/wear.py accounting."""
    rng = np.random.default_rng(seed)
    cfg = dict(n_sets=n_sets, set_ways=16, admit_after_reads=1,
               m_writes=1, window_ops=64, rotate_every=1 << 30)
    a = MonarchKVIndex(KVIndexConfig(**cfg))
    b = MonarchKVIndex(KVIndexConfig(**cfg))
    for step in range(6):
        toks = rng.integers(1, 2000, (2, 8 * CHUNK_TOKENS)).astype(np.int32)
        op = rng.random()
        if op < 0.55:
            fps = np.unique(
                fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
            a.admit_fps(fps)               # one batched device call
            for fp in fps:                 # sequential reference order
                b.admit_fps(np.asarray([fp], np.uint32))
        elif op < 0.85:
            got = a.lookup(toks)
            want = b.lookup(toks)
            np.testing.assert_array_equal(got, want)
        else:
            a._rotate()
            b._rotate()
        sa, sb = _snapshot(a), _snapshot(b)
        for k in sa:
            if isinstance(sa[k], np.ndarray):
                np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
            else:
                assert sa[k] == sb[k], (k, sa[k], sb[k])


def test_clock_rebase_keeps_windows_live():
    """A long-lived op counter folds back before the int32 cycle domain
    wraps, and the t_MWW window keeps expiring/throttling correctly across
    the fold."""
    from repro.core import wear
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=1, set_ways=4, admit_after_reads=0, m_writes=1,
        window_ops=16))
    idx.ops_total = wear.CLOCK_REBASE_AT + 3
    idx.admit_fps(np.arange(1, 9, dtype=np.uint32))
    assert idx.ops_total < wear.CLOCK_REBASE_AT    # clock folded
    assert idx.stats.admissions == 4               # budget still enforced
    assert idx.stats.throttled == 4
    idx.ops_total += 32                            # window expires
    idx.admit_fps(np.arange(100, 103, dtype=np.uint32))
    assert idx.stats.admissions == 7


# ---------------------------------------------------------------------------
# Rotation: device start-gap remap preserves residency; rotation + zipf
# skew levels per-set install wear.
# ---------------------------------------------------------------------------

def test_rotation_remap_preserves_residency(rng):
    """Intentional change vs the seed (documented in kv_index.py): the
    device plane roll moves resident entries WITH the offset bump, so they
    stay searchable after rotation — and stay in agreement with the shadow
    map."""
    idx = MonarchKVIndex(_small_cfg(n_sets=8, set_ways=32))
    toks = rng.integers(1, 4000, (4, 128)).astype(np.int32)
    idx.admit(toks)
    assert idx.lookup(toks).all()
    for _ in range(3):
        idx._rotate()
        got = idx.lookup(toks).reshape(-1)
        want = idx._shadow_hits(
            fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
        np.testing.assert_array_equal(got, want)
        assert got.all()                   # still resident after remap
    # shadow map agrees with the rolled fp planes slot-for-slot
    fp_plane = np.asarray(idx.fp_of)
    for fp, (s, w) in idx.slot_of.items():
        assert fp_plane[s, w] == fp


def _fps_for_set(idx: MonarchKVIndex, n: int, target_set: int) -> np.ndarray:
    """n distinct fingerprints whose (offset-0) home is ``target_set``."""
    out, fp = [], 1
    while len(out) < n:
        cand = np.uint32(fp)
        if int(idx._set_of(np.asarray([cand]))[0]) == target_set:
            out.append(cand)
        fp += 1
    return np.asarray(out, np.uint32)


def test_rotation_levels_skewed_install_wear():
    """Satellite invariant: under a maximally skewed (single-home-set)
    install trace, rotary remapping bounds the max-per-set write count
    relative to the mean; without rotation the wear concentrates."""
    mk = lambda rot: MonarchKVIndex(KVIndexConfig(
        n_sets=8, set_ways=16, admit_after_reads=0, m_writes=1 << 15,
        window_ops=1 << 30, rotate_every=rot))
    hot = mk(1 << 30)
    fps = _fps_for_set(hot, 128, target_set=0)
    for chunk in fps.reshape(8, 16):       # same trace, batch size 16
        hot.admit_fps(chunk)
    w_hot = hot.write_distribution().astype(float)
    assert w_hot.max() / w_hot.mean() == 8.0   # all installs in one set

    lev = mk(16)                           # rotate every 16 admissions
    for chunk in fps.reshape(8, 16):
        lev.admit_fps(chunk)
    assert lev.stats.rotations >= 7
    w_lev = lev.write_distribution().astype(float)
    assert w_lev.sum() == w_hot.sum()      # writes conserved under rotation
    assert w_lev.max() / w_lev.mean() <= 2.0   # leveled across sets


# ---------------------------------------------------------------------------
# Randomized lookup-vs-shadow-map agreement.
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 31), n_sets=st.sampled_from([1, 4, 8]))
def test_lookup_agrees_with_shadow_map(seed, n_sets):
    rng = np.random.default_rng(seed)
    idx = MonarchKVIndex(_small_cfg(n_sets=n_sets, set_ways=32))
    for _ in range(4):
        toks = rng.integers(1, 3000, (2, 128)).astype(np.int32)
        if rng.random() < 0.7:
            idx.admit(toks)
        got = idx.lookup(toks).reshape(-1)
        want = idx._shadow_hits(
            fingerprint_blocks(toks, CHUNK_TOKENS).reshape(-1))
        np.testing.assert_array_equal(got, want)


def test_write_distribution_tracks_admissions(rng):
    idx = MonarchKVIndex(_small_cfg(n_sets=8, set_ways=512))
    for _ in range(4):
        idx.admit(rng.integers(1, 1 << 20, (4, 256)).astype(np.int32))
    dist = idx.write_distribution()
    assert dist.sum() == idx.stats.admissions
    assert (np.asarray(idx.valid).sum(axis=1) == dist).all()


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
