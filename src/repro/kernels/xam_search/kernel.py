"""Pallas TPU kernels for the XAM CAM search — the paper's core primitive
re-thought for the MXU.

Hardware mapping (DESIGN.md §2b): the XAM crossbar answers a search by
summing per-cell XNOR currents down each column and sensing against Ref_S.
On TPU the same inner product is a systolic matmul: encode stored bits and
key bits as ±1, zero out masked key rows, then

    score[q, c] = sum_r K[q, r] * D[r, c]
                = (#matching unmasked bits) - (#mismatching unmasked bits)

so a column matches  iff  score == n_selected[q]  (the integer Ref_S).
One kernel invocation searches a whole superset tile: a (block_q x R) key
block is broadcast against (R x block_c) stored columns entirely in VMEM —
the same "one key vs 512 columns per command" granularity as the paper.

Two scoring paths share the encoding:

* ``int8`` (default): ±1 operands stay int8 and the MXU accumulates into
  int32 (``preferred_element_type=jnp.int32``) — native int8 MXU rate,
  exact integer sense-amp compare, no guard band needed.
* ``f32``: the original float32 path, kept as a fallback flag and pinned
  bit-identical to int8 by tests/test_kernels.py.

Block shapes are MXU-aligned: block_q multiple of 8 (sublanes), block_c a
multiple of 128 (lanes); R (key bits, 64 for a Monarch set) rides in one
block — 64..512 bit keys fit VMEM trivially.

``xam_search_multiset_pallas`` is the device-resident fast path: stored
bits for ALL sets live on device as one (n_sets, R, C) array, and a whole
query batch — each query addressed to its own set — is answered by ONE
``pallas_call``.  Queries are grouped into per-set blocks on the host; the
per-block set ids ride in SMEM (scalar prefetch) and the BlockSpec
index_map uses them to DMA exactly the one stored-bit plane and validity
row each block needs, paged-attention-block-table style.  Validity masking
and the first-match reduction are fused, so the kernel returns a compact
(Q, 1) way index (-1 = miss) instead of a (Q, C) bitmap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_C = 512
MULTISET_BLOCK_Q = 16  # queries per grid step in the fused multi-set kernel


def _unpack_rows(packed):
    """(rp, c) uint8 packed words -> (rp*8, c) int8 {0,1} bits, LSB-first.

    The VMEM-side inverse of ``common.pack_bits_np(..., axis=-2)``:
    logical row ``r`` comes from packed word ``r // 8`` at bit position
    ``r % 8``, so the unpacked plane drops into the existing ±1 encoding
    and the MXU matmul / first-match reduce run unchanged.  Pure VPU
    shift-and-mask — the 8x narrower packed operand is what crossed
    HBM->VMEM."""
    rp, c = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = (packed.astype(jnp.int32)[:, None, :] >> shifts) & 1
    return bits.reshape(rp * 8, c).astype(jnp.int8)


def _check_scoring(scoring: str):
    if scoring not in ("int8", "f32"):
        raise ValueError(
            f"scoring must be one of ('int8', 'f32'), got {scoring!r} "
            "(set via the REPRO_XAM_SCORING env knob or the scoring "
            "argument)")


def _match_bitmap(keys, masks, data, scoring: str):
    """±1-encoded XNOR-current matmul.

    Returns ``(match, n_sel)``: the (bq, bc) int32 {0, 1} match bitmap
    and the (bq, 1) int32 count of selected key bits (the integer
    Ref_S).  The ±1 encoding is built in int32 and only the finished
    {-1, 0, 1} operands narrow to the MXU dtype: Mosaic has no int8
    vector multiply, and bool vectors are kept to select conditions
    (never reduced), which is what the TPU compiler lays out."""
    keys = keys.astype(jnp.int32)
    sel = masks.astype(jnp.int32) != 0
    k_enc = jnp.where(sel, 2 * keys - 1, 0)                   # {-1, 0, 1}
    d_enc = 2 * data.astype(jnp.int32) - 1                    # {-1, 1}
    n_sel = jnp.sum(jnp.where(sel, 1, 0), axis=1, keepdims=True)
    if scoring == "int8":
        score = jax.lax.dot_general(
            k_enc.astype(jnp.int8), d_enc.astype(jnp.int8),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )                                        # int8 x int8 -> int32 MXU
        # Integer sense amp: all-match <=> score == n_sel exactly.
        return jnp.where(score >= n_sel, 1, 0), n_sel
    score = jax.lax.dot_general(
        k_enc.astype(jnp.float32), d_enc.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # All-match  <=>  score == n_sel  (sense amp threshold).  0.5 guard band
    # = half the two-unit gap to a single-mismatch column (analog margin).
    return jnp.where(score >= n_sel.astype(jnp.float32) - 0.5, 1, 0), n_sel


def _xam_search_kernel(keys_ref, data_ref, masks_ref, out_ref, *,
                       scoring: str):
    """keys/masks: (bq, R) int8; data: (R, bc) int8 — or (R//8, bc) uint8
    packed words, unpacked here in VMEM; out: (bq, bc) int8."""
    data = data_ref[...]
    if data.dtype == jnp.uint8:
        data = _unpack_rows(data)
    match, _ = _match_bitmap(keys_ref[...], masks_ref[...], data, scoring)
    out_ref[...] = match.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_c", "scoring", "interpret"))
def xam_search_pallas(
    keys: jnp.ndarray,
    data: jnp.ndarray,
    masks: jnp.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_c: int = DEFAULT_BLOCK_C,
    scoring: str = "int8",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched masked CAM search.  keys/masks (Q, R), data (R, C) int8 —
    or (ceil(R/8), C) uint8 packed words (``plane_format="packed8"``; the
    kernel unpacks in VMEM) -> match bitmap (Q, C) int8.  Q and C are
    padded to block multiples here; callers see exact shapes."""
    q, r = keys.shape
    _check_scoring(scoring)
    packed = data.dtype == jnp.uint8
    if packed:
        rp, c = data.shape
        r_eff = rp * 8
        if r > r_eff:
            raise ValueError(
                f"packed data holds {r_eff} bit rows but keys have {r}")
    else:
        r2, c = data.shape
        assert r == r2
        rp, r_eff = r, r
    assert masks.shape == keys.shape

    bq = min(block_q, _round_up(q, 8))
    bc = min(block_c, _round_up(c, 128))
    qp, cp = _round_up(q, bq), _round_up(c, bc)

    # Keys/masks padded to the unpacked row count: the pad rows carry
    # mask 0, so they never select a bit.
    keys_p = jnp.zeros((qp, r_eff), jnp.int8).at[:q, :r].set(
        keys.astype(jnp.int8))
    # Padded queries: mask all-zero -> they match everything; sliced off.
    masks_p = jnp.zeros((qp, r_eff), jnp.int8).at[:q, :r].set(
        masks.astype(jnp.int8))
    # Padded columns: stored bits 0; harmless, sliced off.
    ddt = jnp.uint8 if packed else jnp.int8
    data_p = jnp.zeros((rp, cp), ddt).at[:, :c].set(data.astype(ddt))

    out = pl.pallas_call(
        functools.partial(_xam_search_kernel, scoring=scoring),
        grid=(qp // bq, cp // bc),
        in_specs=[
            pl.BlockSpec((bq, r_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((rp, bc), lambda i, j: (0, j)),
            pl.BlockSpec((bq, r_eff), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(keys_p, data_p, masks_p)
    return out[:q, :c]


# ---------------------------------------------------------------------------
# Fused multi-set search: one launch serves a query batch spanning sets.
# ---------------------------------------------------------------------------

def _xam_multiset_kernel(block_sets_ref,       # (n_qb,) int32 in SMEM
                         live_blocks_ref,      # (n_qb,) int32 in SMEM
                         keys_ref, masks_ref,  # (bq, R) int8
                         plane_ref,            # (1, R, C) int8 — this block's set
                         valid_ref,            # (1, 1, C) int8
                         out_ref,              # (bq, 1) int32
                         *, scoring: str):
    del block_sets_ref  # consumed by the index maps

    # Padding blocks — the pow2 bucket tail, and in the stacked sharded
    # layout every block a shard pads up to the common Qmax (a per-shard
    # PREFIX of real blocks, so flattened layouts interleave pad runs) —
    # SKIP the matmul entirely and emit the NULL match register.  The
    # scalar-prefetched per-block liveness flags are what make bucket
    # padding nearly free: grid steps still run, compute doesn't.
    blk_live = live_blocks_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(blk_live))
    def _pad_block():
        out_ref[...] = jnp.full(out_ref.shape, -1, jnp.int32)

    @pl.when(blk_live)
    def _live_block():
        plane = plane_ref[0]
        if plane.dtype == jnp.uint8:          # packed8: unpack in VMEM
            plane = _unpack_rows(plane)
        match, n_sel = _match_bitmap(
            keys_ref[...], masks_ref[...], plane, scoring)      # (bq, C)
        live = jnp.where(valid_ref[0].astype(jnp.int32) == 1, match, 0)  # validity
        bq, c = live.shape
        pos = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 1)
        big = jnp.int32(c)
        first = jnp.min(jnp.where(live != 0, pos, big), axis=1,
                        keepdims=True)
        # Ragged block tails (all-zero mask rows) also report -1, so the
        # (Q,) result is deterministic end-to-end, not
        # garbage-where-discarded.
        first = jnp.where(n_sel > 0, first, big)
        out_ref[...] = jnp.where(first < big, first, -1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_q", "scoring", "interpret"))
def xam_search_multiset_pallas(
    keys: jnp.ndarray,        # (Q, R) int8 — block_q-grouped by set (host)
    masks: jnp.ndarray,       # (Q, R) int8 — all-zero rows = padding
    planes: jnp.ndarray,      # (n_sets, R, C) int8 device-resident bits
    valid: jnp.ndarray,       # (n_sets, C) int8 device-resident validity
    block_sets: jnp.ndarray,  # (Q // block_q,) int32 set id per query block
    live_blocks: jnp.ndarray | None = None,  # (Q // block_q,) int32 0 = pad
    *,
    block_q: int = MULTISET_BLOCK_Q,
    scoring: str = "int8",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One fused launch over a set-grouped query batch.  Returns (Q,) int32
    first matching *valid* way per query, -1 = miss.  Q must be a multiple
    of ``block_q`` and every query in block b must belong to set
    ``block_sets[b]``.  ``live_blocks`` (scalar-prefetched alongside the
    block set ids) flags the non-padding blocks: blocks flagged 0 skip
    the matmul and report -1 (as do all-zero-mask rows inside live
    blocks), so both the flat pow2 bucket tail and the stacked sharded
    layout — per-shard prefixes of real blocks, interleaved with pad runs
    when flattened — get a deterministic result at no compute cost for
    the padding.  None = every block live.

    ``planes`` may instead be ``(n_sets, R // 8, C)`` uint8 packed words
    (``plane_format="packed8"``, R a multiple of 8): the kernel unpacks
    each set's plane tile in VMEM, so the HBM->VMEM traffic of the
    dominant plane operand is ~8x lower and the result is bit-identical.
    """
    q, r = keys.shape
    _check_scoring(scoring)
    packed = planes.dtype == jnp.uint8
    n_sets, rp, c = planes.shape
    if packed:
        if r != rp * 8:
            raise ValueError(
                f"packed planes hold {rp * 8} bit rows but keys have {r}; "
                "plane_format='packed8' needs key bits padded to a "
                "multiple of 8")
    else:
        assert r == rp
    assert masks.shape == keys.shape
    assert valid.shape == (n_sets, c)
    assert q % block_q == 0 and block_sets.shape == (q // block_q,)
    if live_blocks is None:
        live_blocks = jnp.ones(q // block_q, jnp.int32)
    assert live_blocks.shape == (q // block_q,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(q // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, r), lambda i, s, nb: (i, 0)),
            pl.BlockSpec((block_q, r), lambda i, s, nb: (i, 0)),
            pl.BlockSpec((1, rp, c), lambda i, s, nb: (s[i], 0, 0)),
            # Validity rides as (n_sets, 1, C) so the gathered block's
            # last two dims equal the array's (the TPU tiling rule).
            pl.BlockSpec((1, 1, c), lambda i, s, nb: (s[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i, s, nb: (i, 0)),
    )
    pdt = jnp.uint8 if packed else jnp.int8
    out = pl.pallas_call(
        functools.partial(_xam_multiset_kernel, scoring=scoring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(block_sets.astype(jnp.int32), live_blocks.astype(jnp.int32),
      keys.astype(jnp.int8), masks.astype(jnp.int8),
      planes.astype(pdt), valid.astype(jnp.int8).reshape(n_sets, 1, c))
    return out[:, 0]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
