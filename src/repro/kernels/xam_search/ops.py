"""Jit'd public wrappers for the XAM search kernels.

``interpret=None`` (the default) is resolved when the kernel is traced:
compiled on a TPU, interpreted on the CPU
(``kernels.common.resolve_interpret``); the flag is threaded, never
hard-coded in callers.  ``scoring`` selects the MXU arithmetic: ``"int8"`` (default — int8 x int8 -> int32
accumulate) or ``"f32"`` (the original float32 path); the default can be
flipped rig-wide via ``REPRO_XAM_SCORING=f32``.
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import autotune
from repro.kernels.common import (
    bucket_pow2, pack_bits_np, plane_format_of, resolve_plane_format)
from repro.kernels.xam_search.kernel import (
    MULTISET_BLOCK_Q, xam_search_multiset_pallas, xam_search_pallas)
from repro.kernels.xam_search.ref import xam_search_ref

#: Host-side fused-search launches since import (every device dispatch of a
#: multi-set search bumps it exactly once — the unsharded single call and
#: the ONE shard_map dispatch of the stacked path).  The dispatch-count
#: tests read and reset it.
LAUNCH_COUNT = 0

#: Host-side ADMISSION launches since import — the write-path twin of
#: ``LAUNCH_COUNT``.  ``MonarchKVIndex`` bumps it once per admission
#: batch, which is one device dispatch at every shard count.
ADMIT_LAUNCH_COUNT = 0

#: Adaptive query-block policy, now MEASURED: ``kernels/autotune.py``
#: answers with the committed per-(shape-bucket, backend, plane-format)
#: winner, falling back deterministically to the original two-point
#: heuristic (16 below 256 queries, 64 at/above) when a family is
#: uncached.  Search results are layout-independent (first-valid-way per
#: query), so the width never changes an answer — pinned by the parity
#: matrix and the cold-cache test.
WIDE_BLOCK_AT = autotune.WIDE_BLOCK_AT
WIDE_BLOCK_Q = autotune.WIDE_BLOCK_Q


def _pick_block_q(n_queries: int, block_q: int | None,
                  plane_format: str = "int8") -> int:
    if block_q is not None:
        return block_q
    return autotune.multiset_block_q(n_queries, plane_format)


def _resolve_scoring(scoring: str | None) -> str:
    if scoring is None:
        scoring = os.environ.get("REPRO_XAM_SCORING", "int8")
    if scoring not in ("int8", "f32"):
        raise ValueError(
            f"scoring must be one of ('int8', 'f32'), got {scoring!r} "
            "(set via the REPRO_XAM_SCORING env knob or the scoring "
            "argument)")
    return scoring


def xam_search(keys, data, masks=None, *, use_kernel: bool = True,
               scoring: str | None = None,
               plane_format: str | None = None,
               interpret: bool | None = None) -> jnp.ndarray:
    """Masked CAM search: (Q,R) keys x (R,C) stored bits -> (Q,C) matches.

    ``plane_format`` (None = the ``REPRO_PLANE_FORMAT`` env knob,
    default ``"int8"``) selects the stored-bit layout: ``"packed8"``
    packs ``data`` 8 bits per uint8 word along R on the host (R padded
    to a multiple of 8 with zero bits) and the kernel unpacks in VMEM —
    bit-identical results, ~8x less plane traffic.  Block shapes come
    from the autotune cache (``kernels/autotune.py``)."""
    keys = jnp.asarray(keys, jnp.int8)
    data = jnp.asarray(data, jnp.int8)
    if masks is None:
        masks = jnp.ones_like(keys)
    masks = jnp.asarray(masks, jnp.int8)
    if not use_kernel:
        return xam_search_ref(keys, data, masks)
    plane_format = resolve_plane_format(plane_format)
    if plane_format == "packed8":
        bits = np.asarray(data, np.int8)
        r, c = bits.shape
        rp8 = -(-r // 8) * 8
        if rp8 != r:
            bits = np.concatenate(
                [bits, np.zeros((rp8 - r, c), np.int8)], axis=0)
        data = jnp.asarray(pack_bits_np(bits, axis=0))
    block_q, block_c = autotune.search_blocks(plane_format)
    return xam_search_pallas(keys, data, masks,
                             block_q=block_q, block_c=block_c,
                             scoring=_resolve_scoring(scoring),
                             interpret=interpret)


def xam_match_index(keys, data, masks=None, **kw) -> jnp.ndarray:
    """First matching column per query; -1 = NULL match register."""
    m = xam_search(keys, data, masks, **kw)
    any_m = jnp.any(m == 1, axis=1)
    return jnp.where(any_m, jnp.argmax(m, axis=1), -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused multi-set fast path (device-resident planes, one launch per batch).
# ---------------------------------------------------------------------------

def _group_one(set_ids: np.ndarray, n_sets: int, block_q: int):
    """Unbucketed per-set block packing (one shard's level-2 grouping).

    Returns ``(slot, block_sets, total_blocks)`` with ``block_sets`` of
    exact length ``total_blocks`` — callers bucket/pad to their own
    compiled-shape policy."""
    set_ids = np.asarray(set_ids, np.int64)
    q = set_ids.shape[0]
    counts = np.bincount(set_ids, minlength=n_sets)
    blocks_per_set = -(-counts // block_q)          # ceil
    total_blocks = int(blocks_per_set.sum())

    block_start = np.zeros(n_sets + 1, np.int64)
    np.cumsum(blocks_per_set, out=block_start[1:])
    set_start = np.zeros(n_sets + 1, np.int64)
    np.cumsum(counts, out=set_start[1:])

    order = np.argsort(set_ids, kind="stable")
    sorted_sets = set_ids[order]
    rank_in_set = np.arange(q, dtype=np.int64) - set_start[sorted_sets]
    slot = np.empty(q, np.int64)
    slot[order] = block_start[sorted_sets] * block_q + rank_in_set

    block_sets = np.repeat(
        np.arange(n_sets, dtype=np.int32), blocks_per_set)
    return slot, block_sets, total_blocks


def group_queries_by_set(set_ids: np.ndarray, n_sets: int,
                         block_q: int = MULTISET_BLOCK_Q):
    """Host-side layout for the fused kernel: pack queries into per-set
    blocks of ``block_q`` and bucket the block count to a power of two (so
    varying batch sizes hit a handful of compiled shapes, not one each).

    Returns ``(slot, block_sets, padded_q, n_blocks)``: query i goes to
    padded row ``slot[i]``; grid block b searches set ``block_sets[b]``;
    only the first ``n_blocks`` blocks are real — the kernel skips the
    matmul for the bucket-padding tail via the scalar-prefetched count.
    """
    slot, block_sets, total_blocks = _group_one(set_ids, n_sets, block_q)
    n_qb = bucket_pow2(max(total_blocks, 1), lo=4)
    padded = np.zeros(n_qb, np.int32)
    padded[:total_blocks] = block_sets
    return slot, padded, n_qb * block_q, total_blocks


def group_queries_by_set_stacked(set_ids: np.ndarray, n_sets: int,
                                 n_parts: int,
                                 block_q: int = MULTISET_BLOCK_Q):
    """Two-level stacked layout for the single-dispatch sharded search.

    Level 1 splits queries by owning shard (``set_id // (n_sets //
    n_parts)`` — contiguous-block ownership, ``geometry.shard_of_set``);
    level 2 packs each shard's queries into per-(local-)set blocks of
    ``block_q`` exactly as :func:`group_queries_by_set` does.  Every
    shard is then padded to ONE common block count — the pow2 bucket of
    the largest shard's block count — so the query operand is a dense
    ``(n_parts, Qmax, R)`` array that shards ``P("sets")`` over the
    mesh, and the jit cache grows with the pow2 bucket count instead of
    one entry per ragged shape.

    Layout contract (consumed by ``xam_search_multiset_stacked``):

    * query i lives at row ``slot[i]`` of shard ``part_of[i]``'s slice;
    * grid block b of shard p searches that shard's LOCAL set
      ``block_sets[p, b]``;
    * only the first ``n_blocks[p]`` blocks of shard p are real — the
      kernel gets ``n_blocks`` via scalar prefetch and reports -1 for
      every padding block/row.

    Returns ``(part_of, slot, block_sets, n_blocks, padded_q)`` with
    ``block_sets`` of shape ``(n_parts, padded_q // block_q)`` and
    ``n_blocks`` of shape ``(n_parts,)``.

    Examples
    --------
    8 global sets over 2 shards, block width 4: set 5 is shard 1's local
    set 1, and the empty shard 0 still occupies its padded slice (zero
    real blocks):

    >>> part_of, slot, block_sets, n_blocks, padded_q = (
    ...     group_queries_by_set_stacked([5, 5, 4], 8, 2, block_q=4))
    >>> part_of.tolist(), slot.tolist()
    ([1, 1, 1], [4, 5, 0])
    >>> block_sets.tolist(), n_blocks.tolist(), padded_q
    ([[0, 0, 0, 0], [0, 1, 0, 0]], [0, 2], 16)
    """
    set_ids = np.asarray(set_ids, np.int64)
    if n_sets % n_parts != 0:
        raise ValueError(f"n_parts={n_parts} must divide n_sets={n_sets}")
    s_part = n_sets // n_parts
    part_of = set_ids // s_part
    grouped = []
    for p in range(n_parts):
        sel = np.nonzero(part_of == p)[0]
        sl, bs, tb = _group_one(set_ids[sel] - p * s_part, s_part, block_q)
        grouped.append((sel, sl, bs, tb))
    n_qb = bucket_pow2(max(max(g[3] for g in grouped), 1), lo=4)
    slot = np.empty(set_ids.shape[0], np.int64)
    block_sets = np.zeros((n_parts, n_qb), np.int32)
    n_blocks = np.zeros(n_parts, np.int32)
    for p, (sel, sl, bs, tb) in enumerate(grouped):
        slot[sel] = sl
        block_sets[p, :tb] = bs
        n_blocks[p] = tb
    return part_of, slot, block_sets, n_blocks, n_qb * block_q


def group_admits_stacked(set_ids: np.ndarray, n_sets: int, n_parts: int,
                         lo: int = 8):
    """Round-grid stacked layout for the single-dispatch admission.

    The admission scan couples candidates ONLY through per-set state
    (residency, window budget, the per-set replacement counter), so two
    candidates targeting different sets commute — only intra-set
    collisions need the sequential tie-break.  This grouping turns that
    into a dense grid: candidate i gets

    * ``part_of[i]`` — its owning storage partition (contiguous-block
      ownership, ``geometry.shard_of_set``);
    * ``row[i]`` — its PER-SET PREFIX RANK (how many earlier candidates
      in the batch target the same set), and
    * ``col[i]`` — its batch-order position among partition
      ``part_of[i]``'s rank-``row[i]`` candidates.

    Packed as a ``(n_parts, n_rounds, round_width)`` operand this is the
    segmented-parallel schedule: round r of a partition holds only
    rank-r candidates, whose sets are pairwise DISTINCT by construction
    (two same-set candidates differ in rank), so a whole round admits
    vectorized while a ``lax.scan`` over rounds replays intra-set
    collisions in exact batch order — bit-equal to the sequential scan.
    Both grid axes are pow2-bucketed (``n_rounds`` from 1, ``round_width``
    from ``lo``) so ragged batches reuse a handful of compiled shapes,
    mirroring :func:`group_queries_by_set_stacked`'s Qmax bucketing.

    Returns ``(part_of, row, col, n_rounds, round_width)``.

    Examples
    --------
    8 global sets over 2 partitions: two set-5 candidates split across
    rounds 0 and 1, the set-4 candidate shares round 0 (distinct set),
    and the set-1 candidate opens partition 0's round 0:

    >>> part_of, row, col, n_rounds, round_width = group_admits_stacked(
    ...     [5, 5, 4, 1], 8, 2)
    >>> part_of.tolist(), row.tolist(), col.tolist()
    ([1, 1, 1, 0], [0, 1, 0, 0], [0, 0, 1, 0])
    >>> n_rounds, round_width
    (2, 8)
    """
    set_ids = np.asarray(set_ids, np.int64)
    if n_sets % n_parts != 0:
        raise ValueError(f"n_parts={n_parts} must divide n_sets={n_sets}")
    s_part = n_sets // n_parts
    part_of = set_ids // s_part
    b = set_ids.shape[0]
    if b == 0:
        return part_of, set_ids.copy(), set_ids.copy(), 1, max(lo, 1)
    # Per-set prefix rank: batch position among same-set candidates.
    set_start = np.zeros(n_sets + 1, np.int64)
    np.cumsum(np.bincount(set_ids, minlength=n_sets), out=set_start[1:])
    order = np.argsort(set_ids, kind="stable")
    row = np.empty(b, np.int64)
    row[order] = np.arange(b) - set_start[set_ids[order]]
    # Column: batch position among the (partition, rank) group's members.
    n_rounds_real = int(row.max()) + 1
    gid = part_of * n_rounds_real + row
    g_start = np.zeros(n_parts * n_rounds_real + 1, np.int64)
    np.cumsum(np.bincount(gid, minlength=n_parts * n_rounds_real),
              out=g_start[1:])
    gorder = np.argsort(gid, kind="stable")
    col = np.empty(b, np.int64)
    col[gorder] = np.arange(b) - g_start[gid[gorder]]
    n_rounds = bucket_pow2(n_rounds_real, lo=1)
    round_width = bucket_pow2(int(col.max()) + 1, lo=lo)
    return part_of, row, col, n_rounds, round_width


def xam_search_multiset(key_bits: np.ndarray, set_ids: np.ndarray,
                        planes: jnp.ndarray, valid: jnp.ndarray, *,
                        block_q: int | None = None,
                        scoring: str | None = None,
                        interpret: bool | None = None) -> np.ndarray:
    """Batched CAM search across sets in ONE kernel launch.

    Parameters
    ----------
    key_bits : np.ndarray, shape (Q, R), {0, 1}
        Host-side query bit rows (one row per fingerprint/key).
    set_ids : np.ndarray, shape (Q,), int
        Which of the device-resident stored-bit planes each query
        searches; values in ``[0, n_sets)``.
    planes : jnp.ndarray, shape (n_sets, R, C) int8 — or (n_sets, R//8,
        C) uint8 packed words (``plane_format="packed8"``; the kernel
        unpacks per tile in VMEM, bit-identical results).  The dtype IS
        the format tag.
    valid : jnp.ndarray, shape (n_sets, C), int8
        Per-way validity; dead ways are masked inside the kernel so they
        never produce hits.
    block_q, scoring, interpret
        Kernel tile width (None = the autotune-cache winner for this
        batch's shape bucket and plane format, falling back to the
        16/64 two-point heuristic — the answer is width-independent),
        MXU arithmetic ("int8" default / "f32"), and Pallas
        interpret-mode flag (None: compiled on a TPU, interpreted on the
        CPU).

    Returns
    -------
    np.ndarray, shape (Q,), int32
        First matching *valid* way per query; ``-1`` = miss.
    """
    block_q = _pick_block_q(len(set_ids), block_q, plane_format_of(planes))
    global LAUNCH_COUNT
    LAUNCH_COUNT += 1
    key_bits = np.asarray(key_bits, np.int8)
    _, r = key_bits.shape
    slot, block_sets, padded_q, n_blocks = group_queries_by_set(
        set_ids, planes.shape[0], block_q)
    keys_p = np.zeros((padded_q, r), np.int8)
    masks_p = np.zeros((padded_q, r), np.int8)
    keys_p[slot] = key_bits
    masks_p[slot] = 1
    # Query-side operands follow the planes' placement.
    put = lambda x: jax.device_put(jnp.asarray(x), planes.sharding)
    live = (np.arange(len(block_sets)) < n_blocks).astype(np.int32)
    out = xam_search_multiset_pallas(
        put(keys_p), put(masks_p), planes, valid,
        put(block_sets), put(live),
        block_q=block_q, scoring=_resolve_scoring(scoring),
        interpret=interpret)
    return np.asarray(out)[slot]


@functools.lru_cache(maxsize=None)
def _stacked_shardmap_fn(mesh: Mesh, block_q: int, scoring: str,
                         interpret: bool):
    """Jitted shard_map wrapper placing every shard's fused search from
    ONE dispatch.  Each mesh device receives its (1, Qmax, R) query slice,
    its scalar-prefetch row of block set ids + valid block count, and its
    resident (sets_per_shard, R, C) plane block; XLA runs the per-shard
    pallas_calls concurrently inside the single program."""
    def per_shard(keys, masks, block_sets, n_blocks, planes, valid):
        live = (jnp.arange(block_sets.shape[1]) < n_blocks[0]
                ).astype(jnp.int32)
        out = xam_search_multiset_pallas(
            keys[0], masks[0], planes, valid, block_sets[0], live,
            block_q=block_q, scoring=scoring, interpret=interpret)
        return out[None]

    spec = (P("sets"),) * 6
    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=spec,
                                 out_specs=P("sets"), check_vma=False))


def xam_search_multiset_stacked(key_bits: np.ndarray, set_ids: np.ndarray,
                                planes: jnp.ndarray, valid: jnp.ndarray, *,
                                mesh: Mesh | None = None,
                                n_parts: int | None = None,
                                block_q: int | None = None,
                                scoring: str | None = None,
                                interpret: bool | None = None) -> np.ndarray:
    """Sharded CAM search in ONE device dispatch (the shard_map fast path).

    The two-level stacked layout of
    :func:`group_queries_by_set_stacked` turns the whole query batch into
    a dense ``(n_parts, Qmax, R)`` operand; with a ``("sets",)`` ``mesh``
    the search is ONE jitted ``shard_map`` call — XLA places every
    shard's fused kernel from a single program.  Without a mesh
    (co-located shards) the same stacked layout flattens into ONE plain
    fused launch over the global planes.

    Parameters
    ----------
    key_bits : np.ndarray, shape (Q, R), {0, 1}
        Host-side query bit rows.
    set_ids : np.ndarray, shape (Q,), int
        GLOBAL physical set ids in ``[0, n_sets)``.
    planes : jnp.ndarray, shape (n_sets, R, C) int8 (or packed uint8 —
        see :func:`xam_search_multiset`)
        Stored bits for ALL sets.  With ``mesh`` this must be sharded
        ``P("sets")`` over it (contiguous blocks, shard k's sets on mesh
        device k — the layout ``MonarchKVIndex`` keeps its planes in);
        without a mesh any single-device array.
    valid : jnp.ndarray, shape (n_sets, C), int8
        Validity planes, sharded like ``planes``.
    mesh : Mesh | None
        The ``("sets",)`` mesh (``launch/mesh.make_set_mesh``).  None =
        single-device host: one flattened fused launch.
    n_parts : int | None
        Shard count of the stacked layout; defaults to the mesh size
        (must equal it when a mesh is given).

    Returns
    -------
    np.ndarray, shape (Q,), int32
        First matching valid way per query (set-local), -1 = miss — same
        contract as :func:`xam_search_multiset`.

    Notes
    -----
    With ``n_parts == 1`` and no mesh this is EXACTLY
    :func:`xam_search_multiset` — same grouping, same kernel — keeping
    the unsharded serving path bit-identical.
    """
    if n_parts is None:
        n_parts = mesh.shape["sets"] if mesh is not None else 1
    if mesh is not None and n_parts != mesh.shape["sets"]:
        raise ValueError(
            f"n_parts={n_parts} must equal the mesh size {mesh.shape['sets']}")
    if n_parts == 1 and mesh is None:
        return xam_search_multiset(key_bits, set_ids, planes, valid,
                                   block_q=block_q, scoring=scoring,
                                   interpret=interpret)
    scoring = _resolve_scoring(scoring)
    block_q = _pick_block_q(len(set_ids), block_q, plane_format_of(planes))
    key_bits = np.asarray(key_bits, np.int8)
    n_sets = planes.shape[0]
    r = key_bits.shape[1]
    part_of, slot, block_sets, n_blocks, padded_q = (
        group_queries_by_set_stacked(set_ids, n_sets, n_parts, block_q))
    keys_p = np.zeros((n_parts, padded_q, r), np.int8)
    masks_p = np.zeros((n_parts, padded_q, r), np.int8)
    keys_p[part_of, slot] = key_bits
    masks_p[part_of, slot] = 1

    global LAUNCH_COUNT
    LAUNCH_COUNT += 1
    if mesh is None:
        # Co-located shards: the stacked layout IS a valid flat grouping
        # once block set ids are globalized — one plain fused launch.
        # Each shard's pad run (blocks past its prefix of real ones, up
        # to the common Qmax) stays flagged dead, so the kernel skips
        # its matmuls exactly like the shard_map path does.
        s_part = n_sets // n_parts
        bs_global = (block_sets
                     + (np.arange(n_parts, dtype=np.int32) * s_part)[:, None])
        n_qb = block_sets.shape[1]
        live = (np.arange(n_qb) < n_blocks[:, None]).astype(np.int32)
        out = xam_search_multiset_pallas(
            jnp.asarray(keys_p.reshape(-1, r)),
            jnp.asarray(masks_p.reshape(-1, r)),
            planes, valid, jnp.asarray(bs_global.reshape(-1)),
            jnp.asarray(live.reshape(-1)),
            block_q=block_q, scoring=scoring, interpret=interpret)
        out = np.asarray(out).reshape(n_parts, padded_q)
    else:
        put = lambda x: jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, P("sets")))
        fn = _stacked_shardmap_fn(mesh, block_q, scoring, interpret)
        out = np.asarray(fn(put(keys_p), put(masks_p), put(block_sets),
                            put(n_blocks), planes, valid))
    return out[part_of, slot].astype(np.int32)


# ---------------------------------------------------------------------------
# Bit-plane packing helpers.
# ---------------------------------------------------------------------------

def words_to_bits(words: jnp.ndarray, n_bits: int = 32) -> jnp.ndarray:
    """(...,) uint words -> (..., n_bits) int8 bit planes (LSB first).
    ``n_bits`` must not exceed the word dtype's width."""
    words = jnp.asarray(words)
    assert n_bits <= jnp.iinfo(words.dtype).bits, "n_bits exceeds word width"
    shifts = jnp.arange(n_bits, dtype=words.dtype)
    return ((words[..., None] >> shifts) & 1).astype(jnp.int8)


def words_to_bits_np(words: np.ndarray, n_bits: int = 32) -> np.ndarray:
    """Host-side twin of :func:`words_to_bits` (no device round-trip).

    >>> words_to_bits_np(np.asarray([5], np.uint32), 4).tolist()
    [[1, 0, 1, 0]]
    """
    words = np.asarray(words)
    assert n_bits <= np.iinfo(words.dtype).bits, "n_bits exceeds word width"
    shifts = np.arange(n_bits, dtype=words.dtype)
    return ((words[..., None] >> shifts) & 1).astype(np.int8)


def bits_to_words(bits: jnp.ndarray) -> jnp.ndarray:
    n_bits = bits.shape[-1]
    shifts = jnp.arange(n_bits, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) << shifts, axis=-1)
