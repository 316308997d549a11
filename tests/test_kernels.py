"""Per-kernel allclose tests: Pallas kernels (interpret mode) vs the
pure-jnp ref.py oracles, swept over shapes/dtypes, plus hypothesis
property tests on the search semantics."""
from __future__ import annotations

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean checkout: deterministic-cases fallback
    from _propcheck import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels.xam_search import ops as xam_ops
from repro.kernels.xam_search.kernel import MULTISET_BLOCK_Q
from repro.kernels.xam_search.ref import (
    xam_search_ref, xam_match_index_ref, xam_search_multiset_ref)
from repro.kernels.hopscotch import ops as hop_ops
from repro.kernels.hopscotch.kernel import BLOCK_Q as HOP_BLOCK_Q
from repro.kernels.hopscotch.ref import hopscotch_lookup_ref
from repro.kernels.string_match import ops as sm_ops
from repro.kernels.string_match.ref import string_match_ref


# ---------------------------------------------------------------------------
# xam_search
# ---------------------------------------------------------------------------

XAM_SHAPES = [
    (1, 8, 8),          # tiny
    (3, 64, 512),       # one Monarch set (odd Q: padding path)
    (8, 64, 512),
    (128, 64, 512),     # one full query block
    (130, 64, 513),     # both dims ragged vs block
    (16, 32, 100),      # narrow key, ragged columns
    (5, 512, 64),       # tall keys
]


@pytest.mark.parametrize("q,r,c", XAM_SHAPES)
def test_xam_search_matches_ref(q, r, c, rng):
    keys = rng.integers(0, 2, (q, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    masks = rng.integers(0, 2, (q, r)).astype(np.int8)
    got = xam_ops.xam_search(keys, data, masks, use_kernel=True)
    want = xam_search_ref(jnp.asarray(keys), jnp.asarray(data),
                          jnp.asarray(masks))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xam_search_planted_matches(rng):
    """Columns explicitly equal to the key must match; single-bit
    corruptions must not."""
    r, c = 64, 512
    key = rng.integers(0, 2, (1, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    data[:, 7] = key[0]
    data[:, 200] = key[0]
    data[:, 201] = key[0]
    data[17, 201] ^= 1  # one-bit mismatch
    out = np.asarray(xam_ops.xam_search(key, data))
    assert out[0, 7] == 1 and out[0, 200] == 1
    assert out[0, 201] == 0


def test_xam_search_mask_widens_matches(rng):
    """Masking out a bit can only ADD matches, never remove them."""
    r, c = 32, 128
    key = rng.integers(0, 2, (1, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    full = np.asarray(xam_ops.xam_search(key, data))
    mask = np.ones((1, r), np.int8)
    mask[0, :16] = 0
    partial = np.asarray(xam_ops.xam_search(key, data, mask))
    assert (partial >= full).all()


def test_xam_all_masked_matches_everything(rng):
    key = rng.integers(0, 2, (2, 16)).astype(np.int8)
    data = rng.integers(0, 2, (16, 64)).astype(np.int8)
    mask = np.zeros((2, 16), np.int8)
    out = np.asarray(xam_ops.xam_search(key, data, mask))
    assert (out == 1).all()


def test_xam_match_index(rng):
    r, c = 32, 96
    keys = rng.integers(0, 2, (4, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    data[:, 50] = keys[2]
    got = np.asarray(xam_ops.xam_match_index(keys, data))
    want = np.asarray(xam_match_index_ref(
        jnp.asarray(keys), jnp.asarray(data), jnp.ones_like(jnp.asarray(keys))))
    np.testing.assert_array_equal(got, want)
    assert got[2] == 50 or data[:, got[2]].tolist() == keys[2].tolist()


@settings(max_examples=30, deadline=None)
@given(q=st.integers(1, 9), r=st.integers(1, 48), c=st.integers(1, 140),
       seed=st.integers(0, 2 ** 31))
def test_xam_search_property(q, r, c, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2, (q, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    masks = rng.integers(0, 2, (q, r)).astype(np.int8)
    got = np.asarray(xam_ops.xam_search(keys, data, masks))
    want = np.asarray(xam_search_ref(
        jnp.asarray(keys), jnp.asarray(data), jnp.asarray(masks)))
    np.testing.assert_array_equal(got, want)


def test_words_bits_roundtrip(rng):
    words = rng.integers(0, 2 ** 32, 64, dtype=np.uint32)
    bits = xam_ops.words_to_bits(jnp.asarray(words), 32)
    back = xam_ops.bits_to_words(bits)
    np.testing.assert_array_equal(np.asarray(back), words)
    np.testing.assert_array_equal(
        xam_ops.words_to_bits_np(words, 32), np.asarray(bits))


@pytest.mark.parametrize("q,r,c", [(3, 64, 512), (64, 32, 128), (1, 8, 8)])
def test_xam_int8_and_f32_scoring_bit_identical(q, r, c, rng):
    """The int8 MXU path and the float32 fallback are pinned equal."""
    keys = rng.integers(0, 2, (q, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    masks = rng.integers(0, 2, (q, r)).astype(np.int8)
    got8 = np.asarray(xam_ops.xam_search(keys, data, masks, scoring="int8"))
    got32 = np.asarray(xam_ops.xam_search(keys, data, masks, scoring="f32"))
    np.testing.assert_array_equal(got8, got32)


# ---------------------------------------------------------------------------
# fused multi-set xam search
# ---------------------------------------------------------------------------

def _random_multiset(rng, n_sets, r, c, n_q, plant_every=3):
    planes = rng.integers(0, 2, (n_sets, r, c)).astype(np.int8)
    valid = rng.integers(0, 2, (n_sets, c)).astype(np.int8)
    words = rng.integers(0, 2 ** 32, n_q, dtype=np.uint32)
    sets = rng.integers(0, n_sets, n_q).astype(np.int32)
    bits = xam_ops.words_to_bits_np(words, r)
    for i in range(0, n_q, plant_every):   # guaranteed valid hits
        w = i % c                          # distinct way per plant in a set
        planes[sets[i], :, w] = bits[i]
        valid[sets[i], w] = 1
    return planes, valid, bits, sets


@pytest.mark.parametrize("n_q", [1, 7, 64, 130, 300])  # 300: wide-block path
@pytest.mark.parametrize("scoring", ["int8", "f32"])
def test_xam_multiset_matches_ref(n_q, scoring, rng):
    n_sets, r, c = 8, 32, 256
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    got = xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid), scoring=scoring)
    want = np.asarray(xam_search_multiset_ref(
        jnp.asarray(bits), jnp.ones_like(jnp.asarray(bits)),
        jnp.asarray(sets), jnp.asarray(planes), jnp.asarray(valid)))
    np.testing.assert_array_equal(got, want)
    assert (got[::3] >= 0).all()           # planted hits found


def test_xam_multiset_validity_fused(rng):
    """A matching column with valid=0 must NOT hit (dead-way masking is
    inside the kernel, not a host-side post-pass)."""
    n_sets, r, c = 2, 16, 128
    planes = np.zeros((n_sets, r, c), np.int8)
    valid = np.zeros((n_sets, c), np.int8)
    word = np.asarray([0xABCD], np.uint32)
    bits = xam_ops.words_to_bits_np(word, r)
    planes[1, :, 5] = bits[0]
    got = xam_ops.xam_search_multiset(
        bits, np.asarray([1]), jnp.asarray(planes), jnp.asarray(valid))
    assert got[0] == -1                    # stored but invalid: miss
    valid[1, 5] = 1
    got = xam_ops.xam_search_multiset(
        bits, np.asarray([1]), jnp.asarray(planes), jnp.asarray(valid))
    assert got[0] == 5


def test_xam_multiset_first_valid_way_wins(rng):
    n_sets, r, c = 1, 16, 128
    planes = np.zeros((n_sets, r, c), np.int8)
    valid = np.zeros((n_sets, c), np.int8)
    word = np.asarray([77], np.uint32)
    bits = xam_ops.words_to_bits_np(word, r)
    for w in (9, 40):
        planes[0, :, w] = bits[0]
        valid[0, w] = 1
    got = xam_ops.xam_search_multiset(
        bits, np.asarray([0]), jnp.asarray(planes), jnp.asarray(valid))
    assert got[0] == 9


@settings(max_examples=15, deadline=None)
@given(n_q=st.integers(1, 40), n_sets=st.sampled_from([1, 3, 8]),
       seed=st.integers(0, 2 ** 31))
def test_xam_multiset_property(n_q, n_sets, seed):
    rng = np.random.default_rng(seed)
    r, c = 16, 128
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    got = xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid))
    want = np.asarray(xam_search_multiset_ref(
        jnp.asarray(bits), jnp.ones_like(jnp.asarray(bits)),
        jnp.asarray(sets), jnp.asarray(planes), jnp.asarray(valid)))
    np.testing.assert_array_equal(got, want)


# Parity matrix (PR 3): ragged / non-power-of-two batch sizes, empty sets
# (no queries and/or no valid columns) and both scoring modes, pinned
# bit-identical against the PER-SET single-plane reference — extends PR 2's
# single-shape bit-identity tests to the whole shape envelope the batched
# admission pipeline exercises.

def _per_set_reference(bits, sets, planes, valid):
    """Loop of single-set xam_search_ref calls + host validity masking +
    first-valid-way reduce — the seed's lookup flow."""
    out = -np.ones(bits.shape[0], np.int32)
    for i in range(bits.shape[0]):
        s = int(sets[i])
        m = np.asarray(xam_search_ref(
            jnp.asarray(bits[i:i + 1]), jnp.asarray(planes[s]),
            jnp.ones((1, bits.shape[1]), jnp.int8)))[0]
        m = m & valid[s]
        hits = np.nonzero(m)[0]
        if hits.size:
            out[i] = hits[0]
    return out


@pytest.mark.parametrize("scoring", ["int8", "f32"])
@pytest.mark.parametrize("n_q,n_sets", [
    (1, 1), (5, 3), (13, 8), (31, 5), (100, 6),
])
def test_xam_multiset_parity_matrix(n_q, n_sets, scoring, rng):
    r, c = 24, 96                          # ragged rows AND columns
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    # half the sets are EMPTY (no valid column at all)...
    valid[::2] = 0
    # ...and (when possible) one set receives no queries
    if n_sets > 1:
        sets[sets == n_sets - 1] = 0
    got = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        scoring=scoring))
    want = _per_set_reference(bits, sets, planes, valid)
    np.testing.assert_array_equal(got, want)
    want_ref = np.asarray(xam_search_multiset_ref(
        jnp.asarray(bits), jnp.ones_like(jnp.asarray(bits)),
        jnp.asarray(sets), jnp.asarray(planes), jnp.asarray(valid)))
    np.testing.assert_array_equal(got, want_ref)


@pytest.mark.parametrize("scoring", ["int8", "f32"])
def test_xam_multiset_all_sets_empty(scoring, rng):
    """Fully empty index (cold start): every query must miss in both
    scoring modes."""
    n_sets, r, c = 4, 16, 128
    planes = np.zeros((n_sets, r, c), np.int8)
    valid = np.zeros((n_sets, c), np.int8)
    bits = xam_ops.words_to_bits_np(
        rng.integers(0, 2 ** 32, 11, dtype=np.uint32), r)
    sets = rng.integers(0, n_sets, 11).astype(np.int32)
    got = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        scoring=scoring))
    assert (got == -1).all()


@pytest.mark.parametrize("n_q", [1, 2, 3, 9, 17, 33, 100])
@pytest.mark.parametrize("window", [8, 32])
def test_hopscotch_parity_matrix(n_q, window, rng):
    """Ragged / non-pow2 batch sizes through the batched hopscotch kernel,
    bit-identical to the per-query reference (dense collisions so
    first-match tie-breaks are actually exercised)."""
    n_slots = window * 16
    t_lo = rng.integers(0, 6, n_slots, dtype=np.uint32)
    t_hi = rng.integers(0, 2, n_slots, dtype=np.uint32)
    homes = rng.integers(0, n_slots - 2 * window, n_q).astype(np.int32)
    q_lo = rng.integers(0, 6, n_q, dtype=np.uint32)
    q_hi = rng.integers(0, 2, n_q, dtype=np.uint32)
    got = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, homes, q_lo, q_hi, window=window))
    want = np.asarray(hopscotch_lookup_ref(
        jnp.asarray(t_lo), jnp.asarray(t_hi), jnp.asarray(homes),
        jnp.asarray(q_lo), jnp.asarray(q_hi), window))
    np.testing.assert_array_equal(got, want)


def test_hopscotch_empty_table(rng):
    """All-EMPTY (zero) table: every non-zero query misses."""
    window, n_q = 16, 9
    t = np.zeros(window * 8, np.uint32)
    homes = rng.integers(0, window * 6, n_q).astype(np.int32)
    q = rng.integers(1, 2 ** 32, n_q, dtype=np.uint32)
    got = np.asarray(hop_ops.hopscotch_lookup(
        t, t, homes, q, q, window=window))
    assert (got == -1).all()


def test_multiset_grouping_layout(rng):
    """Every query lands in a block whose block_set matches its set id."""
    sets = rng.integers(0, 5, 37)
    bq = MULTISET_BLOCK_Q
    slot, block_sets, padded_q, n_blocks = xam_ops.group_queries_by_set(
        sets, 5, bq)
    assert padded_q % bq == 0 and len(block_sets) == padded_q // bq
    assert n_blocks <= padded_q // bq
    assert len(np.unique(slot)) == len(slot)       # injective placement
    for i, s in enumerate(sets):
        assert block_sets[slot[i] // bq] == s
        assert slot[i] // bq < n_blocks            # real rows in real blocks


# ---------------------------------------------------------------------------
# Stacked (single-dispatch sharded) layout — the shapes the shard_map path
# introduces: shards with ZERO queries (Qmax padding only), all-queries-
# one-shard skew, and boundary sets straddling shard edges post-rotation.
# Pinned bit-identical against both the per-set reference and the flat
# fused kernel.
# ---------------------------------------------------------------------------

def test_stacked_grouping_layout(rng):
    """Stacked layout contract: injective (part, slot) placement, local
    block set ids, a common padded Qmax, exact per-part block counts."""
    n_sets, n_parts = 8, 4
    sets = rng.integers(0, n_sets, 41)
    bq = MULTISET_BLOCK_Q
    part_of, slot, block_sets, n_blocks, padded_q = (
        xam_ops.group_queries_by_set_stacked(sets, n_sets, n_parts, bq))
    assert padded_q % bq == 0
    assert block_sets.shape == (n_parts, padded_q // bq)
    assert len({(int(p), int(s)) for p, s in zip(part_of, slot)}) == len(sets)
    s_part = n_sets // n_parts
    for i, s in enumerate(sets):
        p = s // s_part
        assert part_of[i] == p
        assert block_sets[p, slot[i] // bq] == s % s_part
        assert slot[i] // bq < n_blocks[p]         # real rows in real blocks


@pytest.mark.parametrize("scoring", ["int8", "f32"])
@pytest.mark.parametrize("n_parts", [1, 2, 4])
@pytest.mark.parametrize("n_q", [33, 300])     # 300: wide-block path
@pytest.mark.parametrize("case", ["mixed", "one_shard_skew", "empty_shards"])
def test_xam_stacked_parity_matrix(case, n_q, n_parts, scoring, rng):
    """The stacked single-dispatch layout vs the per-set reference and
    the flat fused kernel, over the new edge shapes:

    * ``mixed`` — ragged spread over all shards;
    * ``one_shard_skew`` — every query on ONE shard, all others Qmax==0;
    * ``empty_shards`` — interior shards empty (queries only on the
      outermost shards' boundary sets).
    """
    n_sets, r, c = 8, 24, 96
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    s_part = n_sets // n_parts
    if case == "one_shard_skew":
        sets = (sets % s_part) + (n_parts - 1) * s_part   # last shard only
    elif case == "empty_shards":
        # only the global edge sets 0 and n_sets-1 (first/last shard)
        sets = np.where(sets % 2 == 0, 0, n_sets - 1).astype(sets.dtype)
    got = np.asarray(xam_ops.xam_search_multiset_stacked(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        n_parts=n_parts, scoring=scoring))
    want = _per_set_reference(bits, sets, planes, valid)
    np.testing.assert_array_equal(got, want)
    flat = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        scoring=scoring))
    np.testing.assert_array_equal(got, flat)


@pytest.mark.parametrize("n_parts", [2, 4])
def test_xam_stacked_boundary_sets_post_rotation(n_parts, rng):
    """Sets that straddle shard boundaries after a set+7 rotary remap:
    roll the planes like the serving remap does, address the queries to
    the rotated (boundary-crossing) sets, and require stacked == flat ==
    per-set reference."""
    n_sets, r, c = 8, 24, 96
    planes, valid, bits, _ = _random_multiset(rng, n_sets, r, c, 24)
    shift = 7 % n_sets
    planes = np.roll(planes, shift, axis=0)
    valid = np.roll(valid, shift, axis=0)
    s_part = n_sets // n_parts
    # probe exactly the shard-edge sets (local rows 0 and s_part-1)
    edges = np.asarray(sorted(
        {(k * s_part) % n_sets for k in range(n_parts)} |
        {(k * s_part - 1) % n_sets for k in range(n_parts)}), np.int64)
    sets = edges[rng.integers(0, edges.size, bits.shape[0])]
    got = np.asarray(xam_ops.xam_search_multiset_stacked(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        n_parts=n_parts))
    np.testing.assert_array_equal(got, _per_set_reference(
        bits, sets, planes, valid))
    np.testing.assert_array_equal(got, np.asarray(xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid))))


def test_stacked_compile_cache_capped_at_pow2_buckets():
    """Jit-cache growth pin for the stacked layout: ~40 distinct ragged
    batch sizes collapse onto the pow2 Qmax buckets, so the fused
    kernel's compiled-shape count stays logarithmic (the one-partition
    search obeys the same bucket policy via ``group_queries_by_set``)."""
    import jax
    from repro.kernels.xam_search.kernel import xam_search_multiset_pallas
    rng = np.random.default_rng(0)
    n_sets, r, c = 8, 16, 64
    planes = jnp.asarray(rng.integers(0, 2, (n_sets, r, c)).astype(np.int8))
    valid = jnp.asarray(rng.integers(0, 2, (n_sets, c)).astype(np.int8))
    qs = list(range(1, 80, 2))
    buckets = set()
    for q in qs:
        sets = rng.integers(0, n_sets, q)
        _, _, block_sets, _, padded_q = (
            xam_ops.group_queries_by_set_stacked(sets, n_sets, 2))
        buckets.add((padded_q, block_sets.shape[1]))
    assert len(buckets) <= int(np.log2(max(qs))) + 2, buckets
    jax.clear_caches()
    for q in qs:
        sets = rng.integers(0, n_sets, q)
        bits = xam_ops.words_to_bits_np(
            rng.integers(0, 2 ** 32, q, dtype=np.uint32), r)
        xam_ops.xam_search_multiset_stacked(
            bits, sets, planes, valid, n_parts=2)
    assert xam_search_multiset_pallas._cache_size() <= len(buckets)


def test_batched_block_sizes_meet_floor():
    """Acceptance pin: both fused kernels batch >= 8 queries per grid
    step."""
    assert MULTISET_BLOCK_Q >= 8
    assert HOP_BLOCK_Q >= 8


# ---------------------------------------------------------------------------
# Packed bit-planes (plane_format="packed8"): planes stored 8 logical bits
# per uint8 word along R, unpacked in VMEM per tile.  The contract is
# BIT-IDENTITY with the int8 planes across the whole parity envelope —
# packing is a storage re-layout, never a semantic change.
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip(rng):
    from repro.kernels.common import pack_bits_np, unpack_bits_np
    bits = rng.integers(0, 2, (40, 96)).astype(np.int8)
    packed = pack_bits_np(bits, axis=0)
    assert packed.dtype == np.uint8 and packed.shape == (5, 96)
    np.testing.assert_array_equal(unpack_bits_np(packed, axis=0), bits)
    # LSB-first: logical row r -> word r//8, bit r%8 (words_to_bits order)
    col = np.zeros((8, 1), np.int8)
    col[0, 0] = 1
    col[2, 0] = 1
    assert pack_bits_np(col, axis=0)[0, 0] == 5


def test_pack_bits_rejects_ragged_axis():
    from repro.kernels.common import pack_bits_np
    with pytest.raises(ValueError, match="multiple of 8"):
        pack_bits_np(np.zeros((7, 4), np.int8), axis=0)


def test_plane_format_knob_validation():
    from repro.kernels.common import (PLANE_FORMAT_ENV, plane_format_of,
                                      resolve_plane_format)
    with pytest.raises(ValueError, match=PLANE_FORMAT_ENV):
        resolve_plane_format("packed16")
    with pytest.raises(ValueError, match="dtype"):
        plane_format_of(jnp.zeros((1, 8, 8), jnp.float32))
    assert plane_format_of(jnp.zeros((1, 8, 8), jnp.int8)) == "int8"
    assert plane_format_of(jnp.zeros((1, 1, 8), jnp.uint8)) == "packed8"


def test_plane_format_env_knob_validation(monkeypatch):
    from repro.kernels.common import PLANE_FORMAT_ENV, resolve_plane_format
    monkeypatch.setenv(PLANE_FORMAT_ENV, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        resolve_plane_format(None)
    monkeypatch.setenv(PLANE_FORMAT_ENV, "packed8")
    assert resolve_plane_format(None) == "packed8"


def _packed_planes(planes):
    from repro.kernels.common import pack_bits_np
    return jnp.asarray(pack_bits_np(planes, axis=1))


@pytest.mark.parametrize("scoring", ["int8", "f32"])
@pytest.mark.parametrize("r", [16, 24, 32])
@pytest.mark.parametrize("n_q,n_sets", [
    (1, 1), (5, 3), (13, 8), (31, 5), (100, 6),
])
def test_xam_multiset_packed_parity_matrix(n_q, n_sets, r, scoring, rng):
    """PR-3's parity matrix rerun with packed planes: ragged/non-pow2
    batches, empty sets, a query-less set, both scorings, three key
    widths — packed == int8 == per-set reference, bit for bit."""
    c = 96
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    valid[::2] = 0
    if n_sets > 1:
        sets[sets == n_sets - 1] = 0
    got_p = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, _packed_planes(planes), jnp.asarray(valid),
        scoring=scoring))
    got_i = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, jnp.asarray(planes), jnp.asarray(valid),
        scoring=scoring))
    np.testing.assert_array_equal(got_p, got_i)
    np.testing.assert_array_equal(
        got_p, _per_set_reference(bits, sets, planes, valid))


@pytest.mark.parametrize("scoring", ["int8", "f32"])
def test_xam_multiset_packed_all_sets_empty(scoring, rng):
    n_sets, r, c = 4, 16, 128
    planes = np.zeros((n_sets, r, c), np.int8)
    valid = np.zeros((n_sets, c), np.int8)
    bits = xam_ops.words_to_bits_np(
        rng.integers(0, 2 ** 32, 11, dtype=np.uint32), r)
    sets = rng.integers(0, n_sets, 11).astype(np.int32)
    got = np.asarray(xam_ops.xam_search_multiset(
        bits, sets, _packed_planes(planes), jnp.asarray(valid),
        scoring=scoring))
    assert (got == -1).all()


def test_xam_multiset_packed_rejects_ragged_rows(rng):
    """Packed planes carry no row count of their own, so R must be
    exactly 8x the packed rows — a 20-bit key can't ride a packed plane."""
    planes = np.zeros((2, 3, 64), np.uint8)      # 24 packed rows
    bits = np.zeros((4, 20), np.int8)            # but 20-bit keys
    with pytest.raises(ValueError, match="multiple of 8|packed"):
        xam_ops.xam_search_multiset(
            bits, np.zeros(4, np.int32), jnp.asarray(planes),
            jnp.asarray(np.zeros((2, 64), np.int8)))


@pytest.mark.parametrize("q,r,c", [(3, 64, 512), (16, 32, 100), (130, 64, 513),
                                   (5, 33, 64)])
def test_xam_search_packed_matches_int8(q, r, c, rng):
    """Flat search with packed data planes (host pads ragged R to x8 with
    zero bits; mask-0 pad rows are inert) == the int8 path."""
    keys = rng.integers(0, 2, (q, r)).astype(np.int8)
    data = rng.integers(0, 2, (r, c)).astype(np.int8)
    masks = rng.integers(0, 2, (q, r)).astype(np.int8)
    got_p = np.asarray(xam_ops.xam_search(
        keys, data, masks, plane_format="packed8"))
    got_i = np.asarray(xam_ops.xam_search(
        keys, data, masks, plane_format="int8"))
    np.testing.assert_array_equal(got_p, got_i)


@pytest.mark.parametrize("n_parts", [1, 2, 4])
@pytest.mark.parametrize("case", ["mixed", "one_shard_skew", "empty_shards"])
def test_xam_stacked_packed_parity(case, n_parts, rng):
    """The stacked single-dispatch layout with packed planes, over the
    shard-edge shapes, == per-set reference == flat packed kernel."""
    n_sets, r, c, n_q = 8, 24, 96, 33
    planes, valid, bits, sets = _random_multiset(rng, n_sets, r, c, n_q)
    s_part = n_sets // n_parts
    if case == "one_shard_skew":
        sets = (sets % s_part) + (n_parts - 1) * s_part
    elif case == "empty_shards":
        sets = np.where(sets % 2 == 0, 0, n_sets - 1).astype(sets.dtype)
    got = np.asarray(xam_ops.xam_search_multiset_stacked(
        bits, sets, _packed_planes(planes), jnp.asarray(valid),
        n_parts=n_parts))
    np.testing.assert_array_equal(
        got, _per_set_reference(bits, sets, planes, valid))
    np.testing.assert_array_equal(got, np.asarray(xam_ops.xam_search_multiset(
        bits, sets, _packed_planes(planes), jnp.asarray(valid))))


def test_packed_view_matches_kernel_packing(rng):
    """The functional-model layout twins (core.xam.packed_view) agree with
    the kernel-side numpy packer — ONE packing contract, two layers."""
    from repro.core import xam as xam_model
    from repro.kernels.common import pack_bits_np
    bits = rng.integers(0, 2, (32, 64)).astype(np.int8)
    np.testing.assert_array_equal(
        np.asarray(xam_model.packed_view(jnp.asarray(bits))),
        pack_bits_np(bits, axis=0))
    np.testing.assert_array_equal(
        np.asarray(xam_model.unpacked_view(
            xam_model.packed_view(jnp.asarray(bits)))), bits)


# ---------------------------------------------------------------------------
# hopscotch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [8, 32, 64, 128])
@pytest.mark.parametrize("n_q", [1, 7, 64])
def test_hopscotch_matches_ref(window, n_q, rng):
    n_slots = window * 16
    t_lo = rng.integers(0, 2 ** 32, n_slots, dtype=np.uint32)
    t_hi = rng.integers(0, 2 ** 32, n_slots, dtype=np.uint32)
    homes = rng.integers(0, n_slots - 2 * window, n_q).astype(np.int32)
    q_lo = rng.integers(0, 2 ** 32, n_q, dtype=np.uint32)
    q_hi = rng.integers(0, 2 ** 32, n_q, dtype=np.uint32)
    # plant hits for half the queries at random offsets
    for i in range(0, n_q, 2):
        off = int(rng.integers(0, window))
        q_lo[i] = t_lo[homes[i] + off]
        q_hi[i] = t_hi[homes[i] + off]
    got = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, homes, q_lo, q_hi, window=window))
    want = np.asarray(hopscotch_lookup_ref(
        jnp.asarray(t_lo), jnp.asarray(t_hi), jnp.asarray(homes),
        jnp.asarray(q_lo), jnp.asarray(q_hi), window))
    np.testing.assert_array_equal(got, want)
    for i in range(0, n_q, 2):  # planted hits found
        assert got[i] >= 0


@pytest.mark.parametrize("block_q", [8, 16])
def test_hopscotch_block_q_equivalent(block_q, rng):
    """Any per-step batch size yields the same offsets as the oracle."""
    window, n_q = 16, 27                   # ragged vs both block sizes
    n_slots = window * 16
    t_lo = rng.integers(0, 8, n_slots, dtype=np.uint32)   # dense collisions
    t_hi = rng.integers(0, 2, n_slots, dtype=np.uint32)
    homes = rng.integers(0, n_slots - 2 * window, n_q).astype(np.int32)
    q_lo = rng.integers(0, 8, n_q, dtype=np.uint32)
    q_hi = rng.integers(0, 2, n_q, dtype=np.uint32)
    got = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, homes, q_lo, q_hi, window=window, block_q=block_q))
    want = np.asarray(hopscotch_lookup_ref(
        jnp.asarray(t_lo), jnp.asarray(t_hi), jnp.asarray(homes),
        jnp.asarray(q_lo), jnp.asarray(q_hi), window))
    np.testing.assert_array_equal(got, want)


def test_hopscotch_first_match_wins(rng):
    window = 16
    n_slots = window * 8
    t_lo = np.zeros(n_slots, np.uint32)
    t_hi = np.zeros(n_slots, np.uint32)
    home = 5
    t_lo[home + 3] = 77
    t_lo[home + 9] = 77   # duplicate later in window
    got = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, np.asarray([home], np.int32),
        np.asarray([77], np.uint32), np.asarray([0], np.uint32),
        window=window))
    assert got[0] == 3


@settings(max_examples=25, deadline=None)
@given(window=st.sampled_from([8, 16, 32]), seed=st.integers(0, 2 ** 31))
def test_hopscotch_property(window, seed):
    rng = np.random.default_rng(seed)
    n_slots = window * 8
    t_lo = rng.integers(0, 4, n_slots, dtype=np.uint32)  # dense collisions
    t_hi = rng.integers(0, 2, n_slots, dtype=np.uint32)
    n_q = 16
    homes = rng.integers(0, n_slots - 2 * window, n_q).astype(np.int32)
    q_lo = rng.integers(0, 4, n_q, dtype=np.uint32)
    q_hi = rng.integers(0, 2, n_q, dtype=np.uint32)
    got = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, homes, q_lo, q_hi, window=window))
    want = np.asarray(hopscotch_lookup_ref(
        jnp.asarray(t_lo), jnp.asarray(t_hi), jnp.asarray(homes),
        jnp.asarray(q_lo), jnp.asarray(q_hi), window))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# string_match
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,tile", [
    (100, 3, 64), (4096, 12, 4096), (5000, 12, 1024),
    (8192, 1, 4096),
    # pattern-as-long-as-tile-fraction stress case: ~23s of interpret-mode
    # Pallas on CPU, far beyond what the other cases already cover
    pytest.param(300, 300, 512, marks=pytest.mark.slow),
])
def test_string_match_matches_ref(n, p, tile, rng):
    text = rng.integers(97, 105, n).astype(np.uint8)   # 8 symbols: collisions
    start = int(rng.integers(0, n - p + 1))
    pattern = text[start:start + p].copy()
    got = np.asarray(sm_ops.string_match(text, pattern, tile=tile))
    want = np.asarray(string_match_ref(jnp.asarray(text), jnp.asarray(pattern)))
    np.testing.assert_array_equal(got, want)
    assert got[start] == 1


def test_string_match_vs_python(rng):
    text = bytes(rng.integers(97, 101, 2000).astype(np.uint8))
    pattern = b"abc"
    got = np.asarray(sm_ops.string_match(
        np.frombuffer(text, np.uint8), np.frombuffer(pattern, np.uint8),
        tile=256))
    expect = np.zeros(len(text), np.int8)
    i = text.find(pattern)
    while i != -1:
        expect[i] = 1
        i = text.find(pattern, i + 1)
    np.testing.assert_array_equal(got, expect)


def test_string_match_cross_tile_boundary(rng):
    """A match straddling a tile boundary must be found (halo logic)."""
    tile = 256
    text = np.full(3 * tile, ord("x"), np.uint8)
    pat = np.frombuffer(b"hello", np.uint8)
    pos = tile - 2  # straddles the first boundary
    text[pos:pos + 5] = pat
    got = np.asarray(sm_ops.string_match(text, pat, tile=tile))
    assert got[pos] == 1 and got.sum() == 1


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 31), p=st.integers(1, 20))
def test_string_match_property(seed, p):
    rng = np.random.default_rng(seed)
    n = 512
    text = rng.integers(0, 3, n).astype(np.uint8)  # tiny alphabet
    pattern = rng.integers(0, 3, p).astype(np.uint8)
    got = np.asarray(sm_ops.string_match(text, pattern, tile=128))
    want = np.asarray(string_match_ref(jnp.asarray(text), jnp.asarray(pattern)))
    np.testing.assert_array_equal(got, want)
