"""Monarch address geometry (paper §6, Figures 4 and 7).

Hierarchy (Table 3, 8 GB Monarch):

    8 vaults x 64 banks/vault x 256 supersets/bank x 8 sets/superset
      x 64 rows/set, one row = one 64 B block (512 bits across 8 subarrays).

Supersets are 8x8 grids of 64x64 XAM subarrays; the subarray at (i, j)
belongs to set k = (j - i) % 8 (diagonal arrangement, Fig. 4), which lets a
single 3-to-8 decoder + mode latch select the 8 subarrays of any set for
either row (RowIn) or column (ColumnIn) access.

The rotary wear-leveling offsets (§8) are applied here: vault/bank/superset/
set IDs are rotated by running offsets that the wear controller bumps by
distinct primes on every rotate signal.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

BLOCK_BYTES = 64
SET_WAYS = 512  # columns searchable per set == cache associativity


@dataclasses.dataclass(frozen=True)
class MonarchGeometry:
    """NOTE on Table 3 fidelity: the paper states an 8 GB stack but its
    listed fields (8 vaults x 64 banks x 256 supersets x 8 sets x 64 rows x
    64 B) multiply to 4 GB, and the same table lists both "64 banks/vault"
    and "32 banks/vault".  We keep the STATED capacity (8 GB) — it drives
    the iso-capacity comparisons — by using 512 supersets/bank, and record
    the discrepancy here and in DESIGN.md."""
    n_vaults: int = 8
    banks_per_vault: int = 64
    supersets_per_bank: int = 512
    sets_per_superset: int = 8
    rows_per_set: int = 64
    subarray_rows: int = 64
    subarray_cols: int = 64
    superset_grid: int = 8  # 8x8 subarrays

    @property
    def blocks_per_set(self) -> int:
        return self.rows_per_set

    @property
    def blocks_per_superset(self) -> int:
        return self.sets_per_superset * self.rows_per_set  # 512

    @property
    def total_supersets(self) -> int:
        return self.n_vaults * self.banks_per_vault * self.supersets_per_bank

    @property
    def total_blocks(self) -> int:
        return self.total_supersets * self.blocks_per_superset

    @property
    def capacity_bytes(self) -> int:
        return self.total_blocks * BLOCK_BYTES

    def scaled(self, factor: int) -> "MonarchGeometry":
        """Uniformly scale down vault*bank*superset counts for simulation
        (ratios preserved; per-set geometry untouched)."""
        assert factor >= 1
        ss = max(self.supersets_per_bank // factor, 1)
        return dataclasses.replace(self, supersets_per_bank=ss)


GEOM_8GB = MonarchGeometry()
assert GEOM_8GB.capacity_bytes == 8 * 1024 ** 3


# ---------------------------------------------------------------------------
# Diagonal set selection (Fig. 4).
# ---------------------------------------------------------------------------

def set_of_subarray(i: int | jnp.ndarray, j: int | jnp.ndarray, grid: int = 8):
    """Set id of the subarray at superset grid position (row i, col j)."""
    return (j - i) % grid


def subarrays_of_set(k: int, grid: int = 8):
    """The 8 (i, j) positions selected for set k — one per grid row."""
    return [(i, (i + k) % grid) for i in range(grid)]


def port_select(k: int, mode_column_in: bool, grid: int = 8):
    """Which port (row/column) each selected subarray drives, per the port
    selector's mode latch.  Returns [(i, j, port)] with port in
    {"col", "row"}."""
    port = "col" if mode_column_in else "row"
    return [(i, j, port) for (i, j) in subarrays_of_set(k, grid)]


# ---------------------------------------------------------------------------
# Set-axis sharding (serving): contiguous-block ownership of the set planes.
# The serving index (serve/kv_index.py) splits its n_sets CAM sets across
# n_shards mesh devices; these helpers are THE shard-address arithmetic, so
# host grouping, the admission grid and the rotation remap all agree on
# which shard owns which physical set.
# ---------------------------------------------------------------------------


def sets_per_shard(n_sets: int, n_shards: int) -> int:
    """Sets owned by each shard under contiguous-block ownership.

    Parameters
    ----------
    n_sets : int
        Total (global) CAM set count.
    n_shards : int
        Shard count; must divide ``n_sets`` evenly so every shard's plane
        arrays share one compiled shape.

    Returns
    -------
    int
        ``n_sets // n_shards``.

    Examples
    --------
    >>> sets_per_shard(8, 4)
    2
    """
    if n_shards < 1 or n_sets % n_shards != 0:
        raise ValueError(
            f"n_shards={n_shards} must be >=1 and divide n_sets={n_sets}")
    return n_sets // n_shards


def shard_of_set(set_ids, n_sets: int, n_shards: int):
    """Decompose global physical set ids into ``(shard, local_set)``.

    Shard ``k`` owns the contiguous block of global sets
    ``[k * sets_per_shard, (k + 1) * sets_per_shard)`` — a pure relabeling,
    so the fingerprint -> physical-set mapping (and therefore every hit,
    install and wear decision) is independent of the shard count.

    Parameters
    ----------
    set_ids : array_like of int
        Global physical set ids in ``[0, n_sets)``.
    n_sets, n_shards : int
        Global set count and shard count (``n_shards`` divides ``n_sets``).

    Returns
    -------
    (shard, local) : tuple of arrays
        ``shard[i]`` owns query i's set; ``local[i]`` is the row inside
        that shard's ``(sets_per_shard, ...)`` plane arrays.
    """
    s_local = sets_per_shard(n_sets, n_shards)
    return set_ids // s_local, set_ids % s_local


def shard_set_slice(shard: int, n_sets: int, n_shards: int) -> slice:
    """Global-set slice owned by ``shard`` (contiguous-block ownership)."""
    s_local = sets_per_shard(n_sets, n_shards)
    return slice(shard * s_local, (shard + 1) * s_local)


def shard_roll_plan(shift: int, n_sets: int, n_parts: int):
    """Decompose a GLOBAL cyclic set roll into per-shard collectives.

    The serving index's rotary remap is ``new[g] = old[(g - shift) mod
    n_sets]`` over the whole set axis.  With contiguous-block sharding
    (``s_loc = n_sets // n_parts`` sets per shard) the same permutation
    factors into shard-local arithmetic: write ``shift = q * s_loc + r``
    with ``0 <= r < s_loc``.  Then destination shard ``k`` assembles its
    new plane from exactly TWO sources —

    * rows ``[r, s_loc)``  <- shard ``(k - q) mod n_parts``, rows
      ``[0, s_loc - r)`` (the bulk that stays block-aligned), and
    * rows ``[0, r)``      <- shard ``(k - q - 1) mod n_parts``, rows
      ``[s_loc - r, s_loc)`` (the ``r`` boundary sets that cross a shard
      edge under the global permutation)

    — i.e. each source shard ``j`` ppermutes its low ``s_loc - r`` rows
    to shard ``j + q`` and its high ``r`` rows to shard ``j + q + 1``.
    A slab whose shard permutation is the identity never leaves its
    device: the common small-stride case (``q == 0``) is a pure local
    roll plus a boundary exchange of only the ``r`` edge sets.

    Parameters
    ----------
    shift : int
        Global roll amount in sets (the serving index uses the prime
        stride 7 mod ``n_sets``).
    n_sets, n_parts : int
        Global set count and shard count (``n_parts`` divides
        ``n_sets``).

    Returns
    -------
    (q, r, low_perm, high_perm) : tuple
        ``q``/``r`` as above; ``low_perm``/``high_perm`` are the
        ``(source, destination)`` pair lists for ``jax.lax.ppermute`` of
        the low/high slabs, or ``None`` when that slab stays device-local
        (identity permutation, or — for ``high_perm`` — when ``r == 0``
        and there is no boundary slab at all).

    Examples
    --------
    >>> shard_roll_plan(7, 8, 4)    # stride 7, 2/shard: boundary is local
    (3, 1, [(0, 3), (1, 0), (2, 1), (3, 2)], None)
    >>> shard_roll_plan(1, 8, 4)    # pure boundary exchange
    (0, 1, None, [(0, 1), (1, 2), (2, 3), (3, 0)])
    >>> shard_roll_plan(2, 8, 4)    # whole-block permutation
    (1, 0, [(0, 1), (1, 2), (2, 3), (3, 0)], None)
    """
    s_loc = sets_per_shard(n_sets, n_parts)
    if not 0 < shift < n_sets:
        raise ValueError(f"shift={shift} must be in (0, {n_sets})")
    q, r = divmod(shift, s_loc)
    low_perm = ([(j, (j + q) % n_parts) for j in range(n_parts)]
                if q % n_parts != 0 else None)
    high_perm = ([(j, (j + q + 1) % n_parts) for j in range(n_parts)]
                 if r != 0 and (q + 1) % n_parts != 0 else None)
    return q, r, low_perm, high_perm


# ---------------------------------------------------------------------------
# Rotary offsets (§8): primes per level, vault bumped every 8th rotate.
# ---------------------------------------------------------------------------

ROTATE_PRIMES = {"bank": 1, "set": 3, "vault": 5, "superset": 7}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RotaryOffsets:
    vault: jnp.ndarray  # scalar int32
    bank: jnp.ndarray
    superset: jnp.ndarray
    set_: jnp.ndarray
    rotate_count: jnp.ndarray


def zero_offsets() -> RotaryOffsets:
    # Five DISTINCT zero buffers: sharing one array across fields makes any
    # donated-state op over a fresh state an XLA double-donation error.
    z = lambda: jnp.zeros((), jnp.int32)
    return RotaryOffsets(z(), z(), z(), z(), z())


def apply_rotate(off: RotaryOffsets) -> RotaryOffsets:
    """Bump offsets by the unique primes; vault only every 8 rotates."""
    rc = off.rotate_count + 1
    vault = off.vault + jnp.where(rc % 8 == 0, ROTATE_PRIMES["vault"], 0)
    return RotaryOffsets(
        vault=vault.astype(jnp.int32),
        bank=(off.bank + ROTATE_PRIMES["bank"]).astype(jnp.int32),
        superset=(off.superset + ROTATE_PRIMES["superset"]).astype(jnp.int32),
        set_=(off.set_ + ROTATE_PRIMES["set"]).astype(jnp.int32),
        rotate_count=rc.astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Address decomposition.  Linear block address -> physical coordinates.
# Bit layout (low to high): set-row | set | superset | bank | vault, so that
# consecutive blocks stride rows first (good spatial locality within a set),
# matching the paper's row-major block packing.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockCoord:
    vault: jnp.ndarray
    bank: jnp.ndarray
    superset: jnp.ndarray
    set_: jnp.ndarray
    row: jnp.ndarray

    def flat_superset(self, g: MonarchGeometry) -> jnp.ndarray:
        return (
            self.vault * g.banks_per_vault + self.bank
        ) * g.supersets_per_bank + self.superset


def decompose(block_addr: jnp.ndarray, g: MonarchGeometry,
              off: RotaryOffsets | None = None) -> BlockCoord:
    a = block_addr.astype(jnp.int32) if hasattr(block_addr, "astype") else jnp.asarray(block_addr, jnp.int32)
    row = a % g.rows_per_set
    a = a // g.rows_per_set
    set_ = a % g.sets_per_superset
    a = a // g.sets_per_superset
    superset = a % g.supersets_per_bank
    a = a // g.supersets_per_bank
    bank = a % g.banks_per_vault
    a = a // g.banks_per_vault
    vault = a % g.n_vaults
    if off is not None:
        vault = (vault + off.vault) % g.n_vaults
        bank = (bank + off.bank) % g.banks_per_vault
        superset = (superset + off.superset) % g.supersets_per_bank
        set_ = (set_ + off.set_) % g.sets_per_superset
    to32 = lambda x: x.astype(jnp.int32)
    return BlockCoord(to32(vault), to32(bank), to32(superset), to32(set_), to32(row))


def compose(c: BlockCoord, g: MonarchGeometry) -> jnp.ndarray:
    """Inverse of decompose (without offsets)."""
    a = c.vault.astype(jnp.int32)
    a = a * g.banks_per_vault + c.bank
    a = a * g.supersets_per_bank + c.superset
    a = a * g.sets_per_superset + c.set_
    a = a * g.rows_per_set + c.row
    return a


# ---------------------------------------------------------------------------
# Fig. 7: coordinated RAM <-> CAM mapping for cache mode.  Data blocks live
# in RAM banks; their tags live in CAM banks of the SAME vault with the same
# superset ID.  Every RAM superset (512 blocks) corresponds to one CAM set
# (512 tag columns); the RAM bank ID supplies the CAM set / key / bank bits.
# With 32b tags, each 64-bit column stores two tags; key_id selects which.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CamCoord:
    bank: jnp.ndarray     # CAM bank within the vault's CAM partition
    set_: jnp.ndarray     # set within the CAM superset
    key_id: jnp.ndarray   # which tag of the column (0: low half, 1: high)


def ram_to_cam(ram_bank: jnp.ndarray, g: MonarchGeometry,
               n_cam_banks: int = 2) -> CamCoord:
    """Map a RAM bank id to the (cam_bank, set, key_id) holding its tags.

    The RAM partition has g.banks_per_vault - n_cam_banks banks; each CAM
    set serves one RAM superset; more-significant bits become the key ID to
    minimize mask-register updates (paper §7).
    """
    b = ram_bank.astype(jnp.int32)
    sets_per_cam_bank = g.sets_per_superset * g.supersets_per_bank
    cam_bank = b // (sets_per_cam_bank // max(1, 1))  # folded below
    # Interleave: low bits pick the set, next bit the cam bank, top the key.
    set_ = b % g.sets_per_superset
    rest = b // g.sets_per_superset
    cam_bank = rest % n_cam_banks
    key_id = rest // n_cam_banks
    return CamCoord(cam_bank.astype(jnp.int32), set_.astype(jnp.int32),
                    key_id.astype(jnp.int32))
