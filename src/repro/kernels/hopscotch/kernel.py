"""Pallas TPU kernel: fused hopscotch-window lookup (Monarch flat-CAM flow).

Monarch turns "probe up to H buckets serially" into one CAM search per
window.  The TPU-native analogue is a *scalar-prefetch gather kernel* in the
style of paged attention block tables: the per-query home indices ride in
SMEM (scalar prefetch), and the BlockSpec index_map uses them to DMA exactly
the two H-aligned table tiles that cover each query's window from HBM into
VMEM — one fused gather+match instead of H scalar loads.

Layout: the two uint32 key planes (64-bit keys as lo/hi words) are packed
into one (n_slots/H, 2, H) array so a single gathered block carries both
planes of a tile; query q's window [home, home+H) spans aligned tiles
home//H and home//H + 1.  Both tiles are fetched (two in_specs over the
same packed array), the block's rows are laid side by side as (bq, 2H)
lanes, shifted by home % H, and compared against the query keys.  Output:
first-match offset within each window, or -1.

Grid = BLOCK_Q queries per step.  The seed kernel ran ONE query per grid
step — one DMA round-trip (and, in interpret mode, one Python kernel-body
dispatch) per query.  Here each step owns a block of 8+ queries whose
2*BLOCK_Q window tiles are scalar-prefetch-gathered together and resolved
by ONE vectorized compare+reduce, amortizing per-step overhead the same
way one wide Monarch search command amortizes the command bus.  Query
counts are bucketed to powers of two so ragged batches reuse a handful of
compiled shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

BLOCK_Q = 8   # queries per grid step (acceptance floor: >= 8)


def _lookup_kernel(scalars_ref,   # (3, Q) int32 prefetch (index maps only)
                   qvec_ref,      # (bq, 3) int32: homes, q_lo, q_hi
                   *refs,         # 2*bq packed tiles (1, 2, H) ... + out_ref
                   block_q: int):
    del scalars_ref               # consumed by the index maps
    out_ref = refs[-1]            # (bq, 1) int32
    tiles = refs[:2 * block_q]    # [tile_t, tile_t1] per query

    window = tiles[0].shape[2]
    big = jnp.int32(2 * window)

    qv = qvec_ref[...]
    homes = qv[:, 0:1]            # (bq, 1)
    q_lo = qv[:, 1:2]
    q_hi = qv[:, 2:3]
    off = homes % window

    # Lay each query's two window tiles side by side as one (bq, 2H) lane
    # row per plane, then resolve the whole block with ONE vectorized
    # compare + reduce.
    lo = jnp.concatenate([
        jnp.concatenate([tiles[2 * j][0, 0:1, :], tiles[2 * j + 1][0, 0:1, :]],
                        axis=1)
        for j in range(block_q)], axis=0)             # (bq, 2H)
    hi = jnp.concatenate([
        jnp.concatenate([tiles[2 * j][0, 1:2, :], tiles[2 * j + 1][0, 1:2, :]],
                        axis=1)
        for j in range(block_q)], axis=0)
    pos = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    in_win = (pos >= off) & (pos < off + window)
    match = in_win & (lo == q_lo) & (hi == q_hi)
    first = jnp.min(jnp.where(match, pos, big), axis=1, keepdims=True)
    out_ref[...] = jnp.where(first < big, first - off, -1).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "interpret"))
def hopscotch_lookup_pallas(table_lo, table_hi, homes, q_lo, q_hi,
                            *, window: int, block_q: int = BLOCK_Q,
                            interpret: bool | None = None):
    """table_lo/hi: (n_slots,) uint32 (n_slots % window == 0, with >= window
    pad slots so home+2H never overruns); homes: (Q,) int32; q_lo/hi: (Q,)
    uint32.  Returns (Q,) int32 first-match offsets (-1 = miss)."""
    n_slots = table_lo.shape[0]
    assert n_slots % window == 0
    n_tiles = n_slots // window
    qp = homes.shape[0]
    # Query-count bucketing happens in ops.hopscotch_lookup BEFORE this jit
    # boundary (jit specializes on input shapes, so padding here would not
    # prevent per-batch-size recompiles).
    assert qp % block_q == 0, "pad the query count to block_q multiples"
    scalars = jnp.stack([
        homes.astype(jnp.int32),
        q_lo.astype(jnp.uint32).view(jnp.int32),
        q_hi.astype(jnp.uint32).view(jnp.int32)])

    # Pack both key planes tile-wise: (n_tiles, 2, H), one gather per tile.
    packed = jnp.stack(
        [table_lo.reshape(n_tiles, window).view(jnp.int32),
         table_hi.reshape(n_tiles, window).view(jnp.int32)], axis=1)

    def _tile0(j):
        return pl.BlockSpec(
            (1, 2, window),
            lambda i, s, j=j: (s[0, i * block_q + j] // window, 0, 0))

    def _tile1(j):
        return pl.BlockSpec(
            (1, 2, window),
            lambda i, s, j=j: (s[0, i * block_q + j] // window + 1, 0, 0))

    tile_specs = [s for j in range(block_q) for s in (_tile0(j), _tile1(j))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qp // block_q,),
        in_specs=[pl.BlockSpec((block_q, 3), lambda i, s: (i, 0))]
        + tile_specs,
        out_specs=pl.BlockSpec((block_q, 1), lambda i, s: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, block_q=block_q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(scalars, scalars.T, *([packed] * (2 * block_q)))
    return out[:, 0]
