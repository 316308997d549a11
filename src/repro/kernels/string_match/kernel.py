"""Pallas TPU kernel: broadcast string match (Monarch flat-CAM §10.5).

Monarch broadcasts one search across the whole dataset span, each command
covering up to 4 KB.  TPU mapping: each grid step owns one text tile in VMEM
plus its right halo (the next tile), and slides the pattern across it with P
static vectorized compares on the VPU — one "search command" per tile.

Tile size defaults to 4096 bytes = the paper's per-command search coverage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

TILE = 4096  # bytes per command (paper: "each search covering upto 4KB")


def _make_kernel(pattern_len: int, tile: int):
    def kernel(text_ref, halo_ref, pattern_ref, out_ref):
        # (1, tile) current tile, (1, tile) next tile (the halo), (P,)
        # int32 pattern in SMEM.  Bytes widen to int32 before the compare:
        # the TPU compares 32-bit lanes, and each pattern byte is read as
        # a 32-bit SMEM scalar.
        window = jnp.concatenate([text_ref[...], halo_ref[...]], axis=1)
        window = window.astype(jnp.int32)
        acc = jnp.ones((1, tile), jnp.int32)
        for k in range(pattern_len):  # static unroll: P vector compares
            acc = jnp.where(window[:, k:k + tile] == pattern_ref[k], acc, 0)
        out_ref[...] = acc.astype(out_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("pattern_len", "tile", "interpret"))
def string_match_pallas(text: jnp.ndarray, pattern: jnp.ndarray, *,
                        pattern_len: int, tile: int = TILE,
                        interpret: bool | None = None) -> jnp.ndarray:
    """text: (N,) uint8, pattern: (P,) uint8 (P == pattern_len <= tile).
    Returns (N,) int8 match-start flags."""
    n = text.shape[0]
    assert pattern_len <= tile
    n_tiles = (n + tile - 1) // tile
    padded = (n_tiles + 1) * tile  # one extra tile: halo for the last tile
    text_p = jnp.zeros((1, padded), jnp.uint8).at[0, :n].set(text)

    out = pl.pallas_call(
        _make_kernel(pattern_len, tile),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i + 1)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_tiles * tile), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(text_p, text_p, pattern.astype(jnp.int32))
    res = out[0, :n]
    valid = jnp.arange(n) <= (n - pattern_len)
    return (res.astype(bool) & valid).astype(jnp.int8)
