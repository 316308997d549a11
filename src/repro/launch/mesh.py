"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import geometry


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    # More devices than the mesh needs (single-pod mesh under the 512-device
    # dry-run env): use the first n.
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh() -> Mesh:
    """Whatever this host has (tests / examples): (1, N) data x model."""
    devices = jax.devices()
    return Mesh(np.asarray(devices).reshape(len(devices), 1), ("data", "model"))


def set_partitions(n_shards: int) -> int:
    """Device-partition count for ``n_shards`` logical set shards.

    The serving index shards its global plane arrays contiguously over
    the ``("sets",)`` mesh, so the mesh size must DIVIDE the logical
    shard count (partition boundaries coarsen shard boundaries).  Returns
    the largest divisor of ``n_shards`` that this host's device count can
    hold — 1 on a single-device host, where every logical shard
    co-locates and the index collapses to the unsharded single-launch
    path."""
    devices = len(jax.devices())
    if n_shards <= 1 or devices <= 1:
        return 1
    m = min(n_shards, devices)
    while n_shards % m != 0:
        m -= 1
    return m


def make_set_mesh(n_shards: int) -> Mesh | None:
    """1-D ``("sets",)`` mesh for the sharded ``MonarchKVIndex`` set planes.

    The serving index splits its CAM sets into contiguous blocks (see
    ``geometry.shard_of_set``); each mesh device owns one block's plane
    arrays, wear state and replacement counters, lookup runs as ONE
    ``shard_map``-wrapped fused search over the mesh, and rotation is a
    ``ppermute`` boundary exchange on it.

    Parameters
    ----------
    n_shards : int
        Logical shard count requested by the index.

    Returns
    -------
    Mesh | None
        A mesh over ``set_partitions(n_shards)`` devices with the single
        axis ``"sets"`` (the size always divides ``n_shards``, so
        contiguous ``NamedSharding`` partitions align with shard
        boundaries), or ``None`` when this host has one device (all
        shards co-locate; the index collapses to the unsharded
        single-launch path).  Like every constructor here this touches
        jax device state only when CALLED, never at import.
    """
    n = set_partitions(n_shards)
    if n <= 1:
        return None
    return Mesh(np.asarray(jax.devices()[:n]), ("sets",))


def set_axis_sharding(mesh: Mesh) -> NamedSharding:
    """Contiguous leading-axis sharding over the ``("sets",)`` mesh —
    the layout of the index's plane and per-set arrays."""
    return NamedSharding(mesh, P("sets"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement over ``mesh`` — for small operands every
    shard reads whole (the admission path's traced wear knobs and the
    no-allocate threshold).  Placing them ONCE at index construction keeps
    the per-batch dispatch free of implicit host transfers (the
    ``transfer_guard`` admission pin relies on it)."""
    return NamedSharding(mesh, P())


@functools.lru_cache(maxsize=None)
def make_sharded_roll(mesh: Mesh, n_rows: int, shift: int):
    """Donated on-device cyclic roll of set-sharded plane arrays.

    Builds (and caches) a jitted ``shard_map`` function implementing
    ``new[g] = old[(g - shift) mod n_rows]`` along the leading (set)
    axis of any number of arrays sharded ``P("sets")`` over ``mesh`` —
    the global rotary remap — WITHOUT moving plane data through the
    host: per ``geometry.shard_roll_plan`` each device keeps the
    block-aligned slab local (or ppermutes it whole) and exchanges only
    the ``shift mod sets_per_device`` boundary sets with its neighbor.
    All operands are donated, so the remap is in-place buffer reuse.

    Returns a function ``roll(*arrays) -> tuple`` (one output per input,
    same shapes/shardings).
    """
    m = mesh.shape["sets"]
    s_loc = n_rows // m
    _q, r, low_perm, high_perm = geometry.shard_roll_plan(shift, n_rows, m)

    def _roll_one(x):
        low = x[: s_loc - r] if r else x
        if low_perm is not None:
            low = jax.lax.ppermute(low, "sets", low_perm)
        if r == 0:
            return low
        high = x[s_loc - r:]
        if high_perm is not None:
            high = jax.lax.ppermute(high, "sets", high_perm)
        return jnp.concatenate([high, low], axis=0)

    def _roll(*arrays):
        return tuple(_roll_one(x) for x in arrays)

    jitted = {}   # arity -> jitted donated shard_map (built once, reused)

    def roll(*arrays):
        n = len(arrays)
        if n not in jitted:
            spec = tuple(P("sets") for _ in range(n))
            jitted[n] = jax.jit(
                jax.shard_map(_roll, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False),
                donate_argnums=tuple(range(n)))
        return jitted[n](*arrays)

    return roll


def make_grid_mesh(grid_size: int) -> Mesh | None:
    """1-D mesh over this host's devices for the batched simulator's
    config x trace grid axis.  Returns None when sharding cannot help
    (single device) or cannot be even (grid not divisible by device
    count) — callers fall back to an unsharded vmap."""
    devices = jax.devices()
    n = len(devices)
    if n <= 1 or grid_size % n != 0:
        return None
    return Mesh(np.asarray(devices), ("grid",))
