"""MonarchKVIndex — the paper's technique as a first-class serving feature.

A vLLM-style paged KV prefix cache whose INDEX is a Monarch flat-CAM,
SHARDED along the set axis across a ``("sets",)`` device mesh
(``launch/mesh.make_set_mesh``).  The paper's headline win is in-package
parallelism — many XAM subarrays searched concurrently behind one wide
interface (§III) — and the set axis is exactly that parallelism at serving
scale: shard k owns the contiguous block of physical sets
``[k * sets_per_shard, (k + 1) * sets_per_shard)`` (``geometry.
shard_of_set``) and carries its own stored-bit/validity/fingerprint
planes, D̄&R̄ metadata, per-set replacement counters and §8 ``WearState``
on its own mesh device.

Data flow per batch:

* LOOKUP: every 16-token chunk is fingerprinted (murmur3) and the whole
  batch is answered by ONE device dispatch regardless of the shard
  count: the two-level host grouping emits a stacked ``(n_shards, Qmax,
  R)`` padded layout (per-shard per-set blocks, Qmax pow2-bucketed,
  per-shard valid block counts scalar-prefetched) and
  ``xam_ops.xam_search_multiset_stacked`` wraps the fused multiset
  kernel in a ``shard_map`` over the ``("sets",)`` mesh, so XLA places
  all per-shard searches from a single call — no per-shard host
  round-trips.  With one shard (or all shards co-located on one device)
  the path IS the unsharded fused kernel, bit for bit.  The PR-4 host
  fan-out (one ``pallas_call`` per shard) survives as the differential
  reference behind ``dispatch="fanout"``.
* ADMISSION: like lookup, ONE device dispatch per batch at every shard
  count.  The host packs candidates into the ROUND GRID of
  ``xam_ops.group_admits_stacked`` — a ``(n_parts, n_rounds,
  round_width)`` stacked layout where round r holds each set's rank-r
  candidate (per-set prefix ranks; both axes pow2-bucketed) — and one
  jitted, donated-state dispatch (``shard_map`` over the ``("sets",)``
  mesh when partitions span devices, the plain jitted scan otherwise)
  runs ``_admit_rounds_body``: a ``lax.scan`` over rounds whose step
  admits a whole round VECTORIZED — residency probe, no-allocate gate,
  t_MWW throttle (``core/wear.py`` — the same machinery the Fig. 11
  simulator scans), cold-victim way selection, column install and
  vectorized wear recording (``wear.record_write_rows``).  Decisions
  couple only through per-set state (residency, window budget, the
  per-set replacement counter) and a round's sets are pairwise distinct
  by construction (same-set candidates differ in rank), so the
  round-parallel schedule is bit-equivalent to one global sequential
  scan — the shard-invariance tests replay randomized schedules at
  ``n_shards in {1, 2, 4}`` and require identical hits, installs and
  wear reports.  The PR-5 per-partition ``_admit_batch`` scan survives
  as the differential oracle behind ``admit_dispatch="fanout"``
  (``tests/test_kv_index_differential.py`` pins both paths bit-identical
  after every op).
* ROTATION: the rotary remap is the GLOBAL permutation ``set -> set + 7``
  applied to every shard's planes in lockstep with the ``_set_of`` offset
  bump, so resident entries stay searchable after the remap (pinned since
  the batched-admission PR) and the fingerprint -> physical-set mapping —
  hence wear accounting — is independent of the shard count.  Across
  shards the roll is DEVICE-RESIDENT: per-shard plane rolls plus a
  ``ppermute`` boundary exchange of the sets that cross shard edges
  under the global permutation (``geometry.shard_roll_plan`` /
  ``mesh.make_sharded_roll``) — bits/valid/fp_of/read_after never move
  through the host, and set_writes/WearState track PHYSICAL sets so they
  never move at all.

Intentional change pinned by the shard-invariance tests: the replacement
counter is PER SET (it was one free-running global scalar).  A global
counter couples victim choice in one set to eviction traffic in every
other set — the single cross-set dependency that would make admission
results depend on how sets are sharded.  Per-set counters keep the
§8 "random counter" replacement flavor while making the per-shard scans
exactly equal to the global sequential order.

Asynchronous admission lives in ``serve/admit_queue.py``: ``AdmitQueue``
moves ``admit_fps`` off the serving loop onto a worker thread (installs
overlap model compute), with a drain barrier before rotation and an
optional read-your-writes flush when a looked-up fingerprint is still
pending.

Lifetime targeting: ``KVIndexConfig.with_lifetime`` derives the t_MWW
window length (in ops) from a target lifetime in years, the cell
endurance and an expected op rate — the serving twin of
``wear.make_config``.  ``launch/serve.py`` surfaces it as
``--lifetime-years`` (and the shard count as ``--n-shards``).

The index is exercised by examples/serve_prefix_cache.py and
benchmarks/kernels_bench.py (``kv_index_admit`` pins the batched path
against the pre-batching host loop; ``kv_index_lookup_sharded`` and
``kv_index_admit_async`` pin the sharded fan-out and the queue overlap).
See docs/ARCHITECTURE.md for the paper-concept -> code map and
docs/SERVING.md for the operator guide.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import geometry
from repro.core import lifetime as lifetime_mod
from repro.core import wear
from repro.core.timing import SECONDS_PER_YEAR, t_mww_seconds
from repro.data.pipeline import (fingerprint_blocks, murmur3_np,
                                 prefix_fingerprint_blocks)
from repro.kernels.common import (
    bucket_pow2, pack_bits_np, resolve_plane_format)
from repro.kernels.xam_search import ops as xam_ops
from repro.launch import mesh as mesh_mod

CHUNK_TOKENS = 16
ROTATE_STRIDE = 7          # prime set stride per rotation (§8)
ADMIT_BUCKET_LO = 8        # pow2 bucket floor for admit batch shapes


@dataclasses.dataclass
class KVIndexConfig:
    """Serving-index geometry and §8 durability knobs.

    Parameters
    ----------
    n_sets : int
        CAM sets (global).  Each holds ``set_ways`` searchable columns.
    set_ways : int
        CAM columns (ways) per set — the cache associativity.
    key_bits : int
        Fingerprint bits stored/searched per column.
    admit_after_reads : int
        No-allocate filter: a chunk must be OFFERED this many times
        before it is installed (0 = admit on first touch).
    m_writes : int
        Per-way write budget per t_MWW window; the per-set window budget
        is ``set_ways * m_writes``.
    window_ops : int
        t_MWW window length in CLOCK CYCLES: index ops under
        ``clock="ops"`` (the op counter is the serving cycle proxy),
        wall-clock MICROSECONDS under ``clock="wall"``.
    clock : str
        t_MWW cycle domain (§6.2): ``"ops"`` (default) keeps the
        op-counter proxy — every stamp and window length counts index
        ops, bit-identical to the pre-wall-clock behavior.  ``"wall"``
        expresses the admission window as a latency-era TIME budget:
        stamps are host wall microseconds (``wear.WALL_HZ``), taken once
        per batch on the host so the device scans stay deterministic
        (every candidate in a batch shares the batch's stamp).  Window
        lengths must stay below ``wear.CLOCK_REBASE_AT`` (~17.9 min) —
        the int32 cycle domain's rebase bound.
    rotate_every : int
        Admissions between rotary remaps (prime stride 7).
    n_shards : int
        Set-axis shards; must divide ``n_sets``.  ``1`` (default) is the
        unsharded single-device path, bit-identical to the pre-sharding
        implementation.
    plane_format : str or None
        Stored-bit plane layout (``kernels/common.py``): ``"int8"`` (one
        bit per byte) or ``"packed8"`` (8 bits per uint8 word along the
        key-bit axis — ~8x less HBM->VMEM plane traffic, bit-identical
        results; requires ``key_bits`` divisible by 8).  ``None``
        (default) reads the ``REPRO_PLANE_FORMAT`` env knob.
    fingerprint : str
        Chunk-fingerprint scheme: ``"block"`` (default) hashes each
        16-token chunk independently — right for dedup, where equal
        content is the identity.  ``"prefix"`` chains chunk hashes
        (``data.pipeline.prefix_fingerprint_blocks``) so equal
        fingerprints imply equal ENTIRE prefixes — required whenever the
        index keys KV slabs (a chunk's KV depends on every preceding
        token, so a mid-prompt content match must NOT hit).
    """
    n_sets: int = 32
    set_ways: int = 512           # CAM columns per set
    key_bits: int = 32
    admit_after_reads: int = 1    # no-allocate: admit on 2nd touch
    m_writes: int = 3             # per-way write budget per t_MWW window
    window_ops: int = 4096        # t_MWW window length in clock cycles
    rotate_every: int = 50_000    # admissions between rotary remaps
    n_shards: int = 1             # set-axis mesh shards (divides n_sets)
    plane_format: str | None = None  # None = REPRO_PLANE_FORMAT env knob
    clock: str = "ops"            # t_MWW cycle domain: "ops" | "wall"
    fingerprint: str = "block"    # chunk hashing: "block" | "prefix"

    @classmethod
    def with_lifetime(cls, *, t_life_years: float, endurance: float = 1e8,
                      ops_per_second: float = 1e6, m_writes: int = 3,
                      clock: str = "ops", **kw) -> "KVIndexConfig":
        """Derive ``window_ops`` from a lifetime target (§6.2).

        The t_MWW window in seconds comes from ``core/timing``'s own
        formula ``t_MWW = M * T_life / endurance``.  Under
        ``clock="ops"`` the serving op counter stands in for cycles at
        ``ops_per_second``; under ``clock="wall"`` the window IS the
        time budget, converted straight to wall microseconds
        (``ops_per_second`` is ignored — no rate estimate needed, which
        is the point of the wall clock).

        Parameters
        ----------
        t_life_years : float
            Target index lifetime in years.
        endurance : float
            Cell write endurance (§8 evaluations use 1e8).
        ops_per_second : float
            Expected index op rate (lookup chunks + admission offers per
            second) — converts the window from seconds to ops.  Only
            consulted under ``clock="ops"``.
        m_writes : int
            Per-way write budget per window.
        clock : str
            t_MWW cycle domain, ``"ops"`` or ``"wall"``.
        **kw
            Forwarded to the constructor (``n_sets``, ``n_shards``, ...).

        Returns
        -------
        KVIndexConfig

        Examples
        --------
        >>> cfg = KVIndexConfig.with_lifetime(t_life_years=10.0)
        >>> cfg.window_ops        # 3 * 10y / 1e8 writes * 1e6 ops/s
        9467280
        >>> KVIndexConfig.with_lifetime(
        ...     t_life_years=10.0, clock="wall").window_ops  # 9.467s in us
        9467280
        """
        t_mww_s = t_mww_seconds(m_writes, t_life_years * SECONDS_PER_YEAR,
                                endurance)
        hz = ops_per_second if clock == "ops" else wear.WALL_HZ
        window_ops = max(int(t_mww_s * hz), 1)
        return cls(m_writes=m_writes, window_ops=window_ops, clock=clock,
                   **kw)


@dataclasses.dataclass
class KVIndexStats:
    lookups: int = 0
    chunk_hits: int = 0
    chunk_misses: int = 0
    admissions: int = 0
    admission_skips: int = 0      # no-allocate first touches
    throttled: int = 0            # t_MWW window exhausted
    evictions: int = 0
    rotations: int = 0
    searches: int = 0             # lookup dispatches (1 per batch on the
                                  # single-dispatch paths; 1 per occupied
                                  # shard on the "fanout" reference)
    admit_calls: int = 0          # jitted admit launches (1 per batch on
                                  # the stacked path; 1 per partition
                                  # holding candidates on "fanout")


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _install_column(bits, valid, fp_of, s, w, bitcol, fp):
    """Device-side install of ONE CAM column.  Kept as the pre-batching
    primitive: benchmarks/kernels_bench.py uses it to measure the host-loop
    admission flow the batched pipeline replaced."""
    bits = bits.at[s, :, w].set(bitcol)
    valid = valid.at[s, w].set(jnp.int8(1))
    fp_of = fp_of.at[s, w].set(fp)
    return bits, valid, fp_of


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
def _admit_batch(bits, valid, fp_of, read_after, set_writes, counter,
                 wstate, wdyn, admit_after, sets, fps, bitcols, cycles,
                 touches, active):
    """ONE device call admits a whole (shard-local) candidate batch.

    A ``lax.scan`` over the (order-preserving) candidate list; each step is
    the full per-fingerprint admission pipeline: residency probe ->
    read_after bump | no-allocate gate | t_MWW throttle -> way select ->
    column install fused with §8 wear recording.  Same-set collisions
    resolve through the scan carry (segment conflicts never race — later
    candidates see earlier installs AND earlier evictions: the residency
    and no-allocate decisions are made against the in-batch state, exactly
    as a sequential per-fingerprint loop would), which keeps the batched
    path bit-equivalent to sequential admission.  ``counter`` is the
    PER-SET replacement counter plane (S,) — every decision in the scan
    couples only through per-set state, which is what makes per-shard
    scans equal to one global scan.  ``touches`` carries the host
    first_touch counts (unique fps, so they cannot change mid-batch).
    All mutable planes are donated; outputs feed the host shadow map in
    one transfer.
    """
    n_ways = valid.shape[1]
    iota = jnp.arange(n_ways, dtype=jnp.int32)

    def step(carry, x):
        bits, valid, fp_of, read_after, set_writes, counter, ws = carry
        s, fp, bitcol, cycle, touch, act = x

        vrow = valid[s]
        frow = fp_of[s]
        hitv = (vrow == 1) & (frow == fp)
        is_res = jnp.any(hitv) & act
        res_w = jnp.argmax(hitv).astype(jnp.int32)
        # resident re-offer: D/R metadata only (marks the way re-read).
        read_after = read_after.at[s, res_w].add(
            jnp.where(is_res, 1, 0).astype(jnp.int32))

        # no-allocate gate (D̄&R̄ "never accessed" filter): evaluated against
        # the CURRENT residency, so a fingerprint evicted by an earlier
        # same-batch install re-enters the touch count like the sequential
        # flow would.
        skipped = act & ~is_res & (touch < admit_after)

        # t_MWW lifetime throttle — shared wear machinery (§6.2/§8).
        # window_would_exceed rejects BEFORE the write, so under this
        # policy record_write's lock branch never fires; is_locked is kept
        # as a guard for wear states also driven by other writers.
        locked = wear.is_locked(ws, s, cycle)
        over = wear.window_would_exceed(ws, wdyn, s, cycle)
        throttled = act & ~is_res & ~skipped & (locked | over)
        do_install = act & ~is_res & ~skipped & ~throttled

        # Way selection: first free way, else counter-ordered cold victim
        # (never-re-read ways first — D̄&R̄-style replacement).  The
        # replacement counter free-runs PER SET.
        free = vrow == 0
        has_free = jnp.any(free)
        free_w = jnp.argmax(free).astype(jnp.int32)
        order = ((iota + counter[s]) % n_ways).astype(jnp.int32)
        cold = read_after[s][order] == 0
        victim = jnp.where(jnp.any(cold), order[jnp.argmax(cold)], order[0])
        way = jnp.where(has_free, free_w, victim).astype(jnp.int32)
        evict = do_install & ~has_free
        old_fp = frow[way]
        counter = counter.at[s].add(jnp.where(evict, 1, 0).astype(jnp.int32))

        # Column install (one CAM column + metadata; bitcol arrives in
        # the plane format — packed words scatter as-is).
        bits = bits.at[s, :, way].set(
            jnp.where(do_install, bitcol.astype(bits.dtype),
                      bits[s, :, way]))
        valid = valid.at[s, way].set(
            jnp.where(do_install, 1, vrow[way]).astype(jnp.int8))
        fp_of = fp_of.at[s, way].set(jnp.where(do_install, fp, old_fp))
        read_after = read_after.at[s, way].set(
            jnp.where(do_install, 0, read_after[s, way]).astype(jnp.int32))
        set_writes = set_writes.at[s].add(
            jnp.where(do_install, 1, 0).astype(jnp.int32))

        # Wear recording fused with the install (one implementation: §8's
        # record_write — the same function the Fig. 11 simulator scans).
        ws2, rot, _fl = wear.record_write(ws, wdyn, s, jnp.asarray(True),
                                          cycle)
        ws = jax.tree.map(lambda o, n: jnp.where(do_install, n, o), ws, ws2)

        out = (is_res, skipped, throttled, do_install, way, evict, old_fp)
        return (bits, valid, fp_of, read_after, set_writes, counter, ws), out

    carry = (bits, valid, fp_of, read_after, set_writes, counter, wstate)
    carry, outs = jax.lax.scan(step, carry,
                               (sets, fps, bitcols, cycles, touches, active))
    return carry, outs


def _admit_rounds_body(bits, valid, fp_of, read_after, set_writes, counter,
                       wstate, wdyn, admit_after, sets, fps, bitcols, cycles,
                       touches, active):
    """Segmented-parallel admission over the round grid (ONE partition).

    The candidate operands are ``(n_rounds, round_width)`` grids from
    ``xam_ops.group_admits_stacked``: round r holds each set's rank-r
    candidate, so within a round every active lane targets a DISTINCT
    set.  The ``lax.scan`` over rounds replays intra-set collisions in
    exact batch order (rank order IS batch order within a set) while each
    round's step runs the full per-fingerprint pipeline of
    ``_admit_batch`` vectorized over the lanes — gathers row-clipped,
    installs scattered with an out-of-bounds sentinel so inactive /
    non-installing lanes write nothing, wear recorded via
    ``wear.record_write_rows`` (distinct rows per round is exactly its
    contract).  Because every decision couples only through per-set state,
    the result is bit-identical to the sequential scan — pinned against
    the ``admit_dispatch="fanout"`` oracle after every op.
    """
    n_ways = valid.shape[1]
    s_all = valid.shape[0]
    iota = jnp.arange(n_ways, dtype=jnp.int32)

    def round_step(carry, x):
        bits, valid, fp_of, read_after, set_writes, counter, ws = carry
        s, fp, bitcol, cycle, touch, act = x        # (K,) lanes, one round
        sc = jnp.clip(s, 0, s_all - 1)              # gather-safe row index

        vrow = valid[sc]                            # (K, W)
        frow = fp_of[sc]
        hitv = (vrow == 1) & (frow == fp[:, None])
        is_res = jnp.any(hitv, axis=1) & act
        res_w = jnp.argmax(hitv, axis=1).astype(jnp.int32)
        # resident re-offer: D/R metadata only (marks the way re-read).
        read_after = read_after.at[
            jnp.where(is_res, sc, s_all), res_w].add(1, mode="drop")

        # no-allocate gate (D̄&R̄ "never accessed" filter).
        skipped = act & ~is_res & (touch < admit_after)

        # t_MWW lifetime throttle — same shared wear machinery as the
        # sequential scan (reject-before-write, per-set window).
        locked = wear.is_locked(ws, sc, cycle)
        over = wear.window_would_exceed(ws, wdyn, sc, cycle)
        throttled = act & ~is_res & ~skipped & (locked | over)
        do_install = act & ~is_res & ~skipped & ~throttled

        # Way selection: first free way, else counter-ordered cold victim.
        free = vrow == 0
        has_free = jnp.any(free, axis=1)
        free_w = jnp.argmax(free, axis=1).astype(jnp.int32)
        order = ((iota[None, :] + counter[sc][:, None]) % n_ways
                 ).astype(jnp.int32)
        cold = jnp.take_along_axis(read_after[sc], order, axis=1) == 0
        victim = jnp.where(
            jnp.any(cold, axis=1),
            jnp.take_along_axis(
                order, jnp.argmax(cold, axis=1)[:, None], axis=1)[:, 0],
            order[:, 0])
        way = jnp.where(has_free, free_w, victim).astype(jnp.int32)
        evict = do_install & ~has_free
        old_fp = jnp.take_along_axis(frow, way[:, None], axis=1)[:, 0]
        counter = counter.at[
            jnp.where(evict, sc, s_all)].add(1, mode="drop")

        # Column install: scatter only the installing lanes (sentinel
        # index drops the rest) — rows are distinct within a round, so
        # the scatters never collide.
        ii = jnp.where(do_install, sc, s_all)
        bits = bits.at[ii, :, way].set(bitcol.astype(bits.dtype),
                                       mode="drop")
        valid = valid.at[ii, way].set(jnp.int8(1), mode="drop")
        fp_of = fp_of.at[ii, way].set(fp, mode="drop")
        read_after = read_after.at[ii, way].set(0, mode="drop")
        set_writes = set_writes.at[ii].add(1, mode="drop")

        # Wear recording fused with the install — §8's record_write
        # semantics, vectorized over the round's distinct rows.
        ws = wear.record_write_rows(ws, wdyn, sc, cycle, do_install)

        out = (is_res, skipped, throttled, do_install, way, evict, old_fp)
        return (bits, valid, fp_of, read_after, set_writes, counter, ws), out

    carry = (bits, valid, fp_of, read_after, set_writes, counter, wstate)
    carry, outs = jax.lax.scan(round_step, carry,
                               (sets, fps, bitcols, cycles, touches, active))
    return carry, outs


#: Single-partition entry point for the round-grid admission (donated
#: planes/counters/wear, exactly like ``_admit_batch``).
_admit_rounds = functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))(_admit_rounds_body)


@functools.lru_cache(maxsize=None)
def _admit_shardmap_fn(mesh):
    """Jitted ``shard_map`` wrapper admitting EVERY partition's round grid
    from ONE dispatch — the write-path twin of
    ``xam_ops._stacked_shardmap_fn``.  Each mesh device receives its
    ``P("sets")`` slices: plane/counter blocks, the per-set wear rows, its
    ``(1,)`` block of the stacked wear scalars and its ``(1, n_rounds,
    round_width)`` candidate slice; the traced wear knobs and the
    no-allocate threshold arrive replicated.  The §8 wear state is passed
    DECOMPOSED (per-set rows shard, scalar counters stack) because the
    rotary offsets and rotate totals are invariants of the admission path
    (the serving config disables every rotate signal) and stay outside the
    dispatch entirely.  All state operands are donated."""
    def per_shard(bits, valid, fp_of, read_after, set_writes, counter,
                  swt_w, swt_d, window_writes, window_start, locked_until,
                  wc, ssc, dc, wdyn, admit_after,
                  sets, fps, bitcols, cycles, touches, active):
        ws = wear.WearState(
            swt_w=swt_w, swt_d=swt_d,
            write_counter=wc[0], superset_counter=ssc[0],
            dirty_counter=dc[0],
            offsets=geometry.zero_offsets(),      # invariant; discarded
            window_writes=window_writes, window_start=window_start,
            locked_until=locked_until,
            total_rotates=jnp.zeros((), jnp.int32),
            total_flushed=jnp.zeros((), jnp.int32))
        carry, outs = _admit_rounds_body(
            bits, valid, fp_of, read_after, set_writes, counter, ws, wdyn,
            admit_after, sets[0], fps[0], bitcols[0], cycles[0], touches[0],
            active[0])
        bits, valid, fp_of, read_after, set_writes, counter, ws = carry
        return ((bits, valid, fp_of, read_after, set_writes, counter,
                 ws.swt_w, ws.swt_d, ws.window_writes, ws.window_start,
                 ws.locked_until, ws.write_counter[None],
                 ws.superset_counter[None], ws.dirty_counter[None])
                + tuple(o[None] for o in outs))

    spec = (P("sets"),) * 14 + (P(), P()) + (P("sets"),) * 6
    return jax.jit(
        jax.shard_map(per_shard, mesh=mesh, in_specs=spec,
                      out_specs=P("sets"), check_vma=False),
        donate_argnums=tuple(range(14)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("shift",))
def _rotate_planes(bits, valid, fp_of, read_after, shift: int):
    """Device start-gap-style remap: cyclically shift every set plane by the
    prime stride — resident entries move WITH the ``_set_of`` offset bump,
    so they stay searchable under the rotated mapping.  No host rebuild."""
    roll = lambda x: jnp.roll(x, shift, axis=0)
    return roll(bits), roll(valid), roll(fp_of), roll(read_after)


def _shard_property(name: str, doc: str, settable: bool = True):
    """Global view over a per-partition plane list: partition 0's array
    unwrapped when there is only one (zero-copy — donation-safe for
    external callers like the bench host loop), a host-side concatenation
    in partition order otherwise."""
    def get(self):
        parts = getattr(self, name)
        if len(parts) == 1:
            return parts[0]
        return np.concatenate([np.asarray(p) for p in parts], axis=0)

    def set_(self, value):
        if self.n_parts == 1:
            getattr(self, name)[0] = value
        else:
            setattr(self, name, [
                self._put(np.asarray(value)[self._slice(k)], k)
                for k in range(self.n_parts)])

    return property(get, set_ if settable else None, None, doc)


class KVSlabStore:
    """Host-side KV slab store kept in LOCKSTEP with the index.

    Slabs are keyed by the same ``uint32`` fingerprints the index stores
    in its ``fp_of`` columns, and their lifetime is slaved to the
    admission pipeline: a slab is **staged** when its chunk's KV is
    computed (before the async admission drains), **committed** to
    resident exactly when the fingerprint installs (or refreshes a
    resident entry), **discarded** when the offer is skipped or
    throttled, and **dropped** when the fingerprint's way is evicted.
    Set ROTATION never touches the store: rotation remaps fingerprints
    to new physical sets but evicts nothing, and slab keys are
    fingerprints, not (set, way) slots — so resident slabs survive any
    number of rotations by construction.

    Thread safety: all methods take the store lock; staging (serving
    thread, right after prefill) may race commits (AdmitQueue worker).

    A slab is an arbitrary pytree (per-layer k/v arrays for one chunk);
    the store never inspects it beyond byte accounting.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._staged: dict[int, object] = {}
        self._resident: dict[int, object] = {}

    @staticmethod
    def _nbytes(slab) -> int:
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree.leaves(slab))

    def stage(self, fp: int, slab) -> None:
        """Hold a freshly computed slab until its admission decides."""
        with self._lock:
            self._staged[int(fp)] = slab

    def commit(self, fp: int) -> None:
        """Fingerprint installed (or re-offered while resident): promote
        its staged slab.  No-op when nothing is staged (e.g. a resident
        refresh admitted via the slab-less ``admit()`` path)."""
        with self._lock:
            slab = self._staged.pop(int(fp), None)
            if slab is not None:
                self._resident[int(fp)] = slab

    def discard(self, fp: int) -> None:
        """Offer skipped/throttled/shed: the staged slab is garbage."""
        with self._lock:
            self._staged.pop(int(fp), None)

    def drop(self, fp: int) -> None:
        """Fingerprint evicted from its way: the resident slab dies with
        it (the lockstep half of the index's eviction)."""
        with self._lock:
            self._resident.pop(int(fp), None)

    def get(self, fp: int):
        """Resident slab for ``fp``, or None (staged slabs are NOT
        servable — their admission has not happened yet)."""
        with self._lock:
            return self._resident.get(int(fp))

    def resident_fps(self) -> set[int]:
        with self._lock:
            return set(self._resident)

    def staged_fps(self) -> set[int]:
        with self._lock:
            return set(self._staged)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return sum(self._nbytes(s) for s in self._resident.values())


class MonarchKVIndex:
    """Set-sharded Monarch flat-CAM prefix index (see module docstring).

    Parameters
    ----------
    cfg : KVIndexConfig, optional
        Geometry/durability knobs; default-constructed per instance.
    seed : int
        Reserved for future stochastic policies (placement is currently
        deterministic).
    dispatch : {"auto", "fanout"}
        ``"auto"`` (default): single-dispatch paths — state lives in
        ``n_parts = mesh-partition`` blocks (1 when every shard
        co-locates), lookup is one ``shard_map``/``pallas_call`` launch,
        rotation is the on-device ``ppermute`` boundary exchange.
        ``"fanout"``: the PR-4 reference — one storage block PER LOGICAL
        SHARD, one ``pallas_call`` per shard from the host, rotation
        gathered through the host.  Kept as the differential oracle
        (``tests/test_kv_index_differential.py`` pins both paths
        bit-identical after every op); results never depend on it.
    admit_dispatch : {"auto", "fanout"} or None
        Admission dispatch policy; ``None`` (default) follows
        ``dispatch``.  ``"auto"``: the stacked round-grid path — ONE
        donated device dispatch admits the whole batch at every shard
        count.  ``"fanout"``: the PR-5 per-partition ``_admit_batch``
        scan loop, kept as the admission differential oracle (requires
        no mesh layout, so it is also forced whenever
        ``dispatch="fanout"``).  Results never depend on the choice.
    now_fn : callable, optional
        Wall-clock source for ``clock="wall"`` configs: a zero-arg
        callable returning MONOTONIC seconds as a float (default
        ``time.monotonic``).  Injectable so tests drive the latency-era
        t_MWW window deterministically.  Never consulted under
        ``clock="ops"`` (pinned — the op-clock path is bit-identical to
        the pre-wall-clock implementation).

    Attributes
    ----------
    bits, valid, fp_of, read_after : global views (property)
        The CAM planes — ``(n_sets, key_bits, set_ways)`` int8 stored
        bits (``(n_sets, key_bits // 8, set_ways)`` uint8 packed words
        under ``plane_format="packed8"`` — unpack with
        ``kernels.common.unpack_bits_np(..., axis=1)``),
        ``(n_sets, set_ways)`` validity/fingerprint/D̄&R̄ planes.
        With one partition these are THE device arrays; with several they
        are host-side concatenations of the partition-resident planes
        (read-only use intended; assignment re-splits across partitions).
    n_parts : int
        Device partitions actually holding state: the ``("sets",)`` mesh
        size under ``dispatch="auto"`` (1 on a single-device host —
        co-located shards collapse to the unsharded path), ``n_shards``
        under ``dispatch="fanout"``.
    stats : KVIndexStats
        Host-side operation counters.
    ops_total : int
        The op counter — the t_MWW cycle proxy (lookup chunks + admission
        offers), global across shards.

    Examples
    --------
    >>> import numpy as np
    >>> idx = MonarchKVIndex(KVIndexConfig(
    ...     n_sets=4, set_ways=16, admit_after_reads=0, n_shards=2))
    >>> toks = np.arange(1, 65, dtype=np.int32).reshape(1, 64)
    >>> idx.admit(toks)                       # install 4 chunks
    >>> bool(idx.lookup(toks).all())          # now resident
    True
    """

    def __init__(self, cfg: KVIndexConfig | None = None, seed: int = 0,
                 dispatch: str = "auto", admit_dispatch: str | None = None,
                 now_fn=None, slab_store: KVSlabStore | None = None):
        # cfg default constructed per instance: a shared KVIndexConfig()
        # default would alias mutable config across indexes.
        assert dispatch in ("auto", "fanout"), dispatch
        if admit_dispatch is None:
            admit_dispatch = dispatch
        assert admit_dispatch in ("auto", "fanout"), admit_dispatch
        # "fanout" storage keeps one block per LOGICAL shard (no mesh
        # layout to stack over) — its admission is the per-partition loop.
        assert not (dispatch == "fanout" and admit_dispatch == "auto"), (
            "dispatch='fanout' storage only supports fanout admission")
        self.cfg = KVIndexConfig() if cfg is None else cfg
        c = self.cfg
        if c.clock not in wear.CLOCKS:
            raise ValueError(
                f"KVIndexConfig.clock={c.clock!r}: expected one of "
                f"{wear.CLOCKS}")
        if c.fingerprint not in ("block", "prefix"):
            raise ValueError(
                f"KVIndexConfig.fingerprint={c.fingerprint!r}: expected "
                "'block' or 'prefix'")
        # Optional KV slab store, kept in lockstep by admit_fps's host
        # fold (commit on install, discard on skip/throttle, drop on
        # evict); None = tag-only index (dedup, counting).
        self.slab_store = slab_store
        # t_MWW clock domain.  "ops": the op counter is the cycle proxy
        # (pre-existing semantics, now_fn never consulted).  "wall": cycle
        # stamps are host wall microseconds relative to construction,
        # taken ONCE per admission batch so the device scans see only
        # host-provided constants and stay deterministic (the fanout /
        # stacked differential oracle pins bit-identity between dispatch
        # paths for free — both stamp from the same host read).
        self.clock = c.clock
        self._now_fn = time.monotonic if now_fn is None else now_fn
        self._wall_t0 = self._now_fn() if self.clock == "wall" else 0.0
        self._wall_folded = 0       # cycles removed by clock rebases
        self.dispatch = dispatch
        self.admit_dispatch = admit_dispatch
        self.n_shards = c.n_shards
        self.sets_per_shard = geometry.sets_per_shard(c.n_sets, c.n_shards)
        # ("sets",) mesh placement: partition k's planes/wear live on mesh
        # device k; None on a single-device host — every shard co-locates.
        # Under "auto" state is stored in one block per MESH PARTITION
        # (sharding is a pure relabeling, so coarsening co-located shards
        # into one block changes no result — pinned by the invariance
        # tests), which is what lets lookup run as ONE shard_map dispatch
        # and collapses to the exact unsharded path on one device.  Under
        # "fanout" state keeps one block per logical shard (the PR-4
        # reference paths).
        self.set_mesh = mesh_mod.make_set_mesh(c.n_shards)
        if dispatch == "fanout":
            self.n_parts = c.n_shards
            self._devices = mesh_mod.set_shard_devices(
                self.set_mesh, c.n_shards)
        elif self.set_mesh is None:
            self.n_parts = 1
            self._devices = None
        else:
            self.n_parts = int(self.set_mesh.devices.size)
            self._devices = list(self.set_mesh.devices.flat)
        self._use_shard_map = (dispatch == "auto"
                               and self.set_mesh is not None)
        self.sets_per_part = c.n_sets // self.n_parts
        s_loc = self.sets_per_part
        # Stored-bit plane layout: "int8" keeps one bit per byte;
        # "packed8" stores 8 bits per uint8 word along the key-bit axis
        # (the kernel unpacks per tile in VMEM — installs scatter packed
        # COLUMNS, rolls/ppermutes move packed words, lookup keys stay
        # unpacked).  The planes' dtype is the format tag everywhere
        # downstream.
        self.plane_format = resolve_plane_format(c.plane_format)
        if self.plane_format == "packed8" and c.key_bits % 8 != 0:
            raise ValueError(
                f"plane_format='packed8' needs key_bits divisible by 8, "
                f"got key_bits={c.key_bits}")
        self.plane_rows = (c.key_bits if self.plane_format == "int8"
                           else c.key_bits // 8)
        plane_dtype = (np.int8 if self.plane_format == "int8" else np.uint8)
        # Device-resident CAM state, per partition: fingerprint bits
        # column-wise per set, plus the validity / fingerprint / D-R
        # metadata planes, the PER-SET replacement counters and the
        # per-set install (wear) counters.
        self._bits = [
            self._put(
                np.zeros((s_loc, self.plane_rows, c.set_ways), plane_dtype),
                k)
            for k in range(self.n_parts)]
        self._valid = [
            self._put(np.zeros((s_loc, c.set_ways), np.int8), k)
            for k in range(self.n_parts)]
        self._fp_of = [
            self._put(np.zeros((s_loc, c.set_ways), np.uint32), k)
            for k in range(self.n_parts)]
        self._read_after = [
            self._put(np.zeros((s_loc, c.set_ways), np.int32), k)
            for k in range(self.n_parts)]
        self._set_writes = [
            self._put(np.zeros((s_loc,), np.int32), k)
            for k in range(self.n_parts)]
        self._counters = [
            self._put(np.zeros((s_loc,), np.int32), k)
            for k in range(self.n_parts)]
        # §8 wear state over the physical sets — the simulator's own
        # machinery with serving knobs: window length = window_ops (op-count
        # cycle proxy), budget = set_ways * m_writes, WR/WC/DC rotation
        # signals disabled (serving rotates on the rotate_every cadence).
        # wr_shift=32 actually disables WR — int32 MSB distances never
        # reach 32, so ``rotate_signal`` provably never fires (the default
        # shift of 9 left WR armed despite the stated intent).  That
        # invariance is also what makes the vectorized wear recording of
        # the stacked admission exact (``wear.record_write_rows``).
        # One state per partition, over that partition's sets.
        self.wear_cfg = wear.WearConfig(
            n_supersets=c.n_sets, m_writes=c.m_writes,
            dc_limit=1 << 30, wc_limit=1 << 30, wr_shift=32,
            t_mww_cycles=c.window_ops, blocks_per_superset=c.set_ways,
            clock=c.clock)
        self.wear_dyn = wear.dyn_of(self.wear_cfg)
        self._wear_states = [
            self._put_tree(st, k)
            for k, st in enumerate(wear.shard_states(self.wear_cfg,
                                                     self.n_parts))]
        self._wear_dyns = [self._put_tree(self.wear_dyn, k)
                           for k in range(self.n_parts)]
        self._admit_after = [
            self._put(np.asarray(c.admit_after_reads, np.int32), k)
            for k in range(self.n_parts)]
        if self._use_shard_map and self.n_parts > 1:
            # Replicated once at construction so the per-batch stacked
            # admission dispatch performs no implicit host transfers.
            repl = mesh_mod.replicated_sharding(self.set_mesh)
            self._wdyn_repl = jax.device_put(self.wear_dyn, repl)
            self._admit_after_repl = jax.device_put(
                np.asarray(c.admit_after_reads, np.int32), repl)
        # Host-side policy shadow (map + mirrors): keeps assertions and
        # eviction bookkeeping off the device sync path.
        self.valid_np = np.zeros((c.n_sets, c.set_ways), bool)
        self.fp_of_np = np.zeros((c.n_sets, c.set_ways), np.uint32)
        self.slot_of = {}           # fp -> (set, way) (host-side shadow map)
        self.first_touch = {}       # fp -> touch count (pre-admission)
        self.offset = 0             # rotary set offset
        self.ops_total = 0          # op counter == t_MWW cycle proxy
        self.stats = KVIndexStats()

    # -- sharding plumbing ---------------------------------------------
    def _put(self, x, k: int):
        """Place ``x`` on shard k's mesh device (no-op placement when the
        host has one device, preserving the unsharded dispatch path)."""
        if self._devices is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._devices[k])

    def _put_tree(self, tree, k: int):
        if self._devices is None:
            return tree
        return jax.device_put(tree, self._devices[k])

    def _put_admit(self, x):
        """EXPLICIT single-device placement for stacked-admission grids.

        Unlike :meth:`_put`, which falls back to an implicit
        ``jnp.asarray`` transfer on one-device hosts, this always issues
        an explicit ``jax.device_put`` — so the stacked admission path
        stays legal under ``jax.transfer_guard("disallow")``, which
        blocks only IMPLICIT transfers (the no-host-transfer pin)."""
        dev = self._devices[0] if self._devices is not None else jax.devices()[0]
        return jax.device_put(x, dev)

    def _slice(self, k: int) -> slice:
        """Global-set slice owned by storage partition k."""
        return geometry.shard_set_slice(k, self.cfg.n_sets, self.n_parts)

    def _assemble(self, parts: list) -> jnp.ndarray:
        """Zero-copy GLOBAL jax.Array over the per-partition planes:
        each partition's block is already resident on its mesh device, so
        the contiguous ``P("sets")`` sharded view costs no data movement.
        The assembled array SHARES buffers with ``parts`` — donating it
        (rotation) invalidates them, so callers rebind from the output."""
        if self.n_parts == 1:
            return parts[0]
        shape = (self.cfg.n_sets,) + tuple(parts[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, mesh_mod.set_axis_sharding(self.set_mesh), list(parts))

    def _split_global(self, arr: jnp.ndarray) -> list:
        """Inverse of :meth:`_assemble`: the per-device blocks of a
        ``P("sets")``-sharded global array, in global set order (zero
        copy — each block is a view of the resident shard buffer)."""
        if self.n_parts == 1:
            return [arr]
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return [s.data for s in shards]

    bits = _shard_property("_bits", "stored-bit planes, global view")
    valid = _shard_property("_valid", "validity planes, global view")
    fp_of = _shard_property("_fp_of", "fingerprint planes, global view")
    read_after = _shard_property(
        "_read_after", "D̄&R̄ re-read counters, global view")
    set_writes = _shard_property(
        "_set_writes", "per-set install counters, global view",
        settable=False)
    counter = _shard_property(
        "_counters", "per-set replacement counters, global view",
        settable=False)

    @property
    def wear_state(self) -> wear.WearState:
        """Global §8 wear view: THE shard state when unsharded, else the
        per-set fields concatenated in shard order (see
        ``wear.concat_states``) — reporting only, never write through."""
        return wear.concat_states(self._wear_states)

    # ------------------------------------------------------------------
    def _set_of(self, fps: np.ndarray) -> np.ndarray:
        """Global PHYSICAL set of each fingerprint under the current
        rotary offset — independent of the shard count by construction
        (sharding only relabels who stores a set)."""
        base = murmur3_np(fps) % np.uint32(self.cfg.n_sets)
        return ((base.astype(np.int64) + self.offset) % self.cfg.n_sets
                ).astype(np.int32)

    def _bitcols(self, fps: np.ndarray) -> np.ndarray:
        """Install columns in the PLANE format: ``(B, key_bits)`` int8
        bit rows, or ``(B, key_bits // 8)`` uint8 packed words under
        ``plane_format="packed8"`` (for 32-bit keys the packed column is
        just the fingerprint's little-endian bytes — same LSB-first
        contract as ``words_to_bits``)."""
        cols = xam_ops.words_to_bits_np(fps, self.cfg.key_bits)
        if self.plane_format == "packed8":
            return pack_bits_np(cols, axis=-1)
        return cols

    def _clock_cycles(self) -> int:
        """Current t_MWW cycle stamp in the config's clock domain: the op
        counter under ``clock="ops"``; elapsed wall MICROSECONDS since
        construction (minus rebased folds) under ``clock="wall"``."""
        if self.clock == "ops":
            return self.ops_total
        return (int((self._now_fn() - self._wall_t0) * wear.WALL_HZ)
                - self._wall_folded)

    def _maybe_rebase_clock(self):
        """Fold the t_MWW clock before the int32 cycle domain wraps
        (timestamps shift in lockstep, so window/lock decisions are
        unchanged).  Op clock: a ~2.1e9-op serving instance would
        otherwise see its windows stop expiring and throttle forever.
        Wall clock: the same fold fires every ~17.9 minutes
        (``CLOCK_REBASE_AT`` microseconds), keeping any window below that
        bound exact indefinitely."""
        if self._clock_cycles() < wear.CLOCK_REBASE_AT:
            return
        for k in range(self.n_parts):
            self._wear_states[k] = wear.rebase_clock(
                self._wear_states[k], wear.CLOCK_REBASE_AT)
        if self.clock == "ops":
            self.ops_total -= wear.CLOCK_REBASE_AT
        else:
            self._wall_folded += wear.CLOCK_REBASE_AT

    def fingerprints(self, tokens: np.ndarray) -> np.ndarray:
        """(B, S) tokens -> (B, S//16) uint32 chunk fingerprints under
        this index's configured scheme (``cfg.fingerprint``).  Every
        caller that feeds fingerprints back to this index (AdmitQueue,
        resume engine, benches) MUST hash through here so lookup,
        admission and slab keys agree."""
        if self.cfg.fingerprint == "prefix":
            return prefix_fingerprint_blocks(tokens, CHUNK_TOKENS)
        return fingerprint_blocks(tokens, CHUNK_TOKENS)

    def lookup(self, tokens: np.ndarray) -> np.ndarray:
        """Probe the index for every whole 16-token chunk of a batch.

        Parameters
        ----------
        tokens : np.ndarray, shape (B, S), int
            Token ids; only complete ``CHUNK_TOKENS``-sized chunks are
            fingerprinted.

        Returns
        -------
        np.ndarray, shape (B, S // 16), bool
            True where the chunk's KV is already cached.  ONE device
            dispatch for the whole batch: the fused multiset kernel
            (one partition) or its ``shard_map`` wrapping over the
            ``("sets",)`` mesh (the stacked layout).  The ``"fanout"``
            reference dispatches one call per shard holding queries.
        """
        self._maybe_rebase_clock()
        fps = self.fingerprints(tokens)
        flat = fps.reshape(-1)
        self.stats.lookups += 1
        if flat.size == 0:
            return np.zeros(fps.shape, bool)
        sets = self._set_of(flat)
        key_bits = xam_ops.words_to_bits_np(
            flat.astype(np.uint32), self.cfg.key_bits)
        if self._use_shard_map and self.n_parts > 1:
            ways = xam_ops.xam_search_multiset_stacked(
                key_bits, sets, self._assemble(self._bits),
                self._assemble(self._valid), mesh=self.set_mesh)
            self.stats.searches += 1
        elif self.n_parts == 1:
            ways = xam_ops.xam_search_multiset(
                key_bits, sets, self._bits[0], self._valid[0])
            self.stats.searches += 1
        else:
            ways = xam_ops.xam_search_multiset_sharded(
                key_bits, sets, self._bits, self._valid)
            self.stats.searches += len(
                np.unique(sets // self.sets_per_part))
        hit = ways >= 0
        self.stats.chunk_hits += int(hit.sum())
        self.stats.chunk_misses += int((~hit).sum())
        self.ops_total += int(flat.shape[0])   # t_MWW cycle proxy advances
        return hit.reshape(fps.shape)

    def _shadow_hits(self, flat_fps: np.ndarray) -> np.ndarray:
        """Oracle for lookup(): hits according to the host shadow map."""
        return np.asarray([int(fp) in self.slot_of for fp in flat_fps], bool)

    # ------------------------------------------------------------------
    def admit(self, tokens: np.ndarray):
        """Offer a batch's chunks for admission (after KV was computed).

        Fingerprints are uniqued (order-preserved) and forwarded to
        :meth:`admit_fps` — O(1) jitted device calls per shard regardless
        of batch size."""
        fps = np.unique(self.fingerprints(tokens).reshape(-1))
        self.admit_fps(fps)

    def _admit_one(self, fp: np.uint32):
        """Single-fingerprint compatibility shim over the batched path."""
        self.admit_fps(np.asarray([fp], np.uint32))

    def admit_fps(self, fps: np.ndarray):
        """Batched admission of (unique, order-preserved) fingerprints.

        Parameters
        ----------
        fps : np.ndarray, shape (B,), uint32
            Candidate fingerprints.  MUST be unique within the call (the
            no-allocate touch counts are latched per batch); ``admit``
            uniques for you.

        Notes
        -----
        With ``admit_dispatch="auto"`` (the default) the whole batch is
        admitted by ONE donated device dispatch at every shard count: the
        host packs candidates into the round grid of
        ``xam_ops.group_admits_stacked`` (cycle stamps keep their global
        batch position) and ``_admit_rounds_body`` admits round after
        round, each round vectorized over its (pairwise-distinct-set)
        lanes.  ``admit_dispatch="fanout"`` keeps the per-partition
        ``_admit_batch`` scan loop as the oracle.  Because every decision
        couples only through per-set state, both are bit-equivalent to
        admitting the same fingerprints one at a time in batch order, at
        any shard count (and any partitioning of shards onto devices).
        """
        fps = np.asarray(fps, np.uint32)
        b = int(fps.size)
        if b == 0:
            return
        self._maybe_rebase_clock()
        sets = self._set_of(fps)
        touches = np.asarray(
            [self.first_touch.get(int(fp), 0) for fp in fps], np.int32)
        bitcols = self._bitcols(fps)
        # t_MWW cycle stamps, computed ONCE here so both dispatch paths
        # stamp identically (the differential oracle pins this).  Op
        # clock: each candidate's global batch position.  Wall clock: one
        # host timestamp for the whole batch — the device scan sees only
        # host constants either way, so it stays deterministic.
        if self.clock == "ops":
            cycles = (self.ops_total + np.arange(b)).astype(np.int32)
        else:
            cycles = np.full(b, self._clock_cycles(), np.int32)
        if self.admit_dispatch == "auto":
            skip, thr, inst, way, evict, old_fp = self._admit_stacked(
                fps, sets, touches, bitcols, cycles)
        else:
            skip, thr, inst, way, evict, old_fp = self._admit_fanout(
                fps, sets, touches, bitcols, cycles)
        self.ops_total += b

        # Host shadow-map fold, in GLOBAL batch order.  (Every shadow-map
        # operation on a given fingerprint — install, touch bump, evict of
        # its slot — happens inside its one owning partition, so batch
        # order and the fanout path's partition-major order produce the
        # same shadow state.)  The slab store folds in lockstep: a
        # victim's slab dies with its way, a staged slab becomes resident
        # exactly when its fingerprint installs (or refreshes a resident
        # way), and is discarded on skip/throttle so rejected KV never
        # serves a hit.
        store = self.slab_store
        for i in range(b):
            if evict[i]:
                self.slot_of.pop(int(old_fp[i]), None)
                if store is not None:
                    store.drop(int(old_fp[i]))
            fp = int(fps[i])
            was_resident = fp in self.slot_of
            if skip[i]:
                self.first_touch[fp] = self.first_touch.get(fp, 0) + 1
            if inst[i]:
                s, w = int(sets[i]), int(way[i])
                self.slot_of[fp] = (s, w)
                self.first_touch.pop(fp, None)
                self.valid_np[s, w] = True
                self.fp_of_np[s, w] = fps[i]
            if store is not None:
                if inst[i] or was_resident:
                    store.commit(fp)
                else:
                    store.discard(fp)
        batch_installs = int(inst.sum())
        self.stats.admissions += batch_installs
        self.stats.admission_skips += int(skip.sum())
        self.stats.evictions += int(evict.sum())
        self.stats.throttled += int(thr.sum())

        # Rotate when the admission count crosses a rotate_every multiple
        # (a plain modulo check would skip the boundary whenever a batch
        # jumps over it).  At most one remap per admit call — batched
        # rotation lands at the batch boundary rather than mid-sequence;
        # the equivalence test pins auto-rotation off for that reason.
        prev = self.stats.admissions - batch_installs
        if (self.stats.admissions // self.cfg.rotate_every
                > prev // self.cfg.rotate_every):
            self._rotate()

    def _admit_stacked(self, fps, sets, touches, bitcols, cycles):
        """ONE-dispatch admission over the stacked round grid.

        Packs the batch into the ``(n_parts, n_rounds, round_width)``
        grid of ``xam_ops.group_admits_stacked`` (pow2-bucketed on both
        candidate axes so repeated batch sizes reuse compilations), then
        launches a single donated device call: the jitted
        ``_admit_rounds`` scan when one partition holds everything, else
        the ``_admit_shardmap_fn`` shard_map over the set mesh.  Returns
        the per-candidate decision arrays in GLOBAL batch order."""
        c = self.cfg
        b = int(fps.size)
        part_of, row, col, n_rounds, round_width = (
            xam_ops.group_admits_stacked(
                sets, c.n_sets, self.n_parts, lo=ADMIT_BUCKET_LO))
        idx = (part_of, row, col)
        g = (self.n_parts, n_rounds, round_width)
        sets_g = np.zeros(g, np.int32)
        sets_g[idx] = sets - part_of * self.sets_per_part  # partition-local
        fps_g = np.zeros(g, np.uint32)
        fps_g[idx] = fps
        bit_g = np.zeros(g + (self.plane_rows,), bitcols.dtype)
        bit_g[idx] = bitcols
        cyc_g = np.full(g, cycles[0], np.int32)      # pad lanes: inactive
        cyc_g[idx] = cycles                          # host-stamped, per batch
        tch_g = np.zeros(g, np.int32)
        tch_g[idx] = touches
        act_g = np.zeros(g, bool)
        act_g[idx] = True

        xam_ops.ADMIT_LAUNCH_COUNT += 1
        self.stats.admit_calls += 1
        if self._use_shard_map and self.n_parts > 1:
            outs = self._dispatch_stacked_shardmap(
                sets_g, fps_g, bit_g, cyc_g, tch_g, act_g)
        else:
            put = self._put_admit
            carry, outs = _admit_rounds(
                self._bits[0], self._valid[0], self._fp_of[0],
                self._read_after[0], self._set_writes[0], self._counters[0],
                self._wear_states[0], self._wear_dyns[0],
                self._admit_after[0],
                put(sets_g[0]), put(fps_g[0]), put(bit_g[0]), put(cyc_g[0]),
                put(tch_g[0]), put(act_g[0]))
            (self._bits[0], self._valid[0], self._fp_of[0],
             self._read_after[0], self._set_writes[0], self._counters[0],
             self._wear_states[0]) = carry

        # One sync for the whole batch; un-grid back to batch order.
        outs_np = [np.asarray(o) for o in jax.device_get(outs)]
        sel = idx if outs_np[0].ndim == 3 else (row, col)
        _res, skip, thr, inst, way, evict, old_fp = (
            o[sel] for o in outs_np)
        return skip, thr, inst, way, evict, old_fp

    def _dispatch_stacked_shardmap(self, sets_g, fps_g, bit_g, cyc_g,
                                   tch_g, act_g):
        """Run the stacked admission grid as ONE ``shard_map`` dispatch.

        Assembles the per-partition planes/counters into zero-copy
        ``P("sets")`` global views, decomposes the §8 wear states (per-set
        rows assemble like planes; scalar counters stack to an
        ``(n_parts,)`` array from fresh per-device ``(1,)`` reshapes, so
        donation never invalidates live state), places the candidate
        grids sharded on their leading partition axis, and calls the
        cached ``_admit_shardmap_fn``.  Every transfer here is an
        EXPLICIT ``device_put`` (the wear knobs and no-allocate threshold
        were replicated once at construction), keeping the per-batch path
        legal under ``jax.transfer_guard("disallow")``.  Rebinds all
        donated state from the outputs and returns the stacked decision
        grids."""
        mesh = self.set_mesh
        shd = mesh_mod.set_axis_sharding(mesh)
        ws = self._wear_states

        def stack_scalar(field):
            # jnp.reshape emits a FRESH (1,) buffer on each scalar's
            # resident device — the assembled stack can be donated
            # without invalidating the live wear states.
            return jax.make_array_from_single_device_arrays(
                (self.n_parts,), shd,
                [jnp.reshape(getattr(w, field), (1,)) for w in ws])

        fn = _admit_shardmap_fn(mesh)
        out = fn(
            self._assemble(self._bits), self._assemble(self._valid),
            self._assemble(self._fp_of), self._assemble(self._read_after),
            self._assemble(self._set_writes), self._assemble(self._counters),
            self._assemble([w.swt_w for w in ws]),
            self._assemble([w.swt_d for w in ws]),
            self._assemble([w.window_writes for w in ws]),
            self._assemble([w.window_start for w in ws]),
            self._assemble([w.locked_until for w in ws]),
            stack_scalar("write_counter"), stack_scalar("superset_counter"),
            stack_scalar("dirty_counter"),
            self._wdyn_repl, self._admit_after_repl,
            jax.device_put(sets_g, shd), jax.device_put(fps_g, shd),
            jax.device_put(bit_g, shd), jax.device_put(cyc_g, shd),
            jax.device_put(tch_g, shd), jax.device_put(act_g, shd))

        parts = [self._split_global(o) for o in out[:14]]
        (self._bits, self._valid, self._fp_of, self._read_after,
         self._set_writes, self._counters) = parts[:6]
        sww_p, swd_p, wwr_p, wst_p, lck_p, wc_p, ssc_p, dc_p = parts[6:]
        # Rotary offsets / rotate totals never entered the dispatch (the
        # serving config disables every rotate signal), so the old
        # buffers are still live — reattach them.
        self._wear_states = [
            wear.WearState(
                swt_w=sww_p[k], swt_d=swd_p[k],
                write_counter=jnp.reshape(wc_p[k], ()),
                superset_counter=jnp.reshape(ssc_p[k], ()),
                dirty_counter=jnp.reshape(dc_p[k], ()),
                offsets=old.offsets,
                window_writes=wwr_p[k], window_start=wst_p[k],
                locked_until=lck_p[k],
                total_rotates=old.total_rotates,
                total_flushed=old.total_flushed)
            for k, old in enumerate(self._wear_states)]
        return out[14:]

    def _admit_fanout(self, fps, sets, touches, bitcols, cycles):
        """PR-5 per-partition admission oracle (``admit_dispatch="fanout"``).

        Groups candidates by owning storage partition (original order
        preserved within each group; cycle stamps keep their global batch
        position) and runs ONE donated ``_admit_batch`` scan per
        partition holding candidates — dispatched back-to-back, synced
        together.  Returns the decision arrays scattered back to GLOBAL
        batch order, so the shared shadow-map fold in ``admit_fps`` is
        identical for both dispatch modes."""
        b = int(fps.size)
        shard_ids = sets // self.sets_per_part
        skip = np.zeros(b, bool)
        thr = np.zeros(b, bool)
        inst = np.zeros(b, bool)
        evict = np.zeros(b, bool)
        way = np.zeros(b, np.int32)
        old_fp = np.zeros(b, np.uint32)
        launches = []
        for k in np.unique(shard_ids):
            k = int(k)
            sel = np.nonzero(shard_ids == k)[0]
            bk = sel.size
            bb = bucket_pow2(bk, lo=ADMIT_BUCKET_LO)
            fps_p = np.zeros(bb, np.uint32)
            fps_p[:bk] = fps[sel]
            sets_p = np.zeros(bb, np.int32)
            sets_p[:bk] = sets[sel] - k * self.sets_per_part  # local rows
            bit_p = np.zeros((bb, self.plane_rows), bitcols.dtype)
            bit_p[:bk] = bitcols[sel]
            cycles_p = np.full(bb, cycles[0], np.int32)  # pad: inactive
            cycles_p[:bk] = cycles[sel]              # host-stamped, per batch
            touch_p = np.zeros(bb, np.int32)
            touch_p[:bk] = touches[sel]
            active = np.zeros(bb, bool)
            active[:bk] = True

            carry, outs = _admit_batch(
                self._bits[k], self._valid[k], self._fp_of[k],
                self._read_after[k], self._set_writes[k], self._counters[k],
                self._wear_states[k], self._wear_dyns[k],
                self._admit_after[k],
                self._put(sets_p, k), self._put(fps_p, k),
                self._put(bit_p, k), self._put(cycles_p, k),
                self._put(touch_p, k), self._put(active, k))
            (self._bits[k], self._valid[k], self._fp_of[k],
             self._read_after[k], self._set_writes[k], self._counters[k],
             self._wear_states[k]) = carry
            xam_ops.ADMIT_LAUNCH_COUNT += 1
            self.stats.admit_calls += 1
            launches.append((sel, outs))

        for sel, outs in launches:
            bk = sel.size
            _res, sk, th, in_, wy, ev, of = (
                np.asarray(o)[:bk] for o in outs)
            skip[sel] = sk
            thr[sel] = th
            inst[sel] = in_
            way[sel] = wy
            evict[sel] = ev
            old_fp[sel] = of
        return skip, thr, inst, way, evict, old_fp

    def _rotate(self):
        """Rotary remap (prime stride 7): shift the set planes by the
        GLOBAL permutation ``set -> set + 7 (mod n_sets)`` while the
        ``_set_of`` offset moves in lockstep, so resident entries stay
        searchable under the rotated placement and the physical mapping is
        identical at every shard count.  One partition: ONE donated device
        roll.  Across partitions: DEVICE-RESIDENT — each shard donates a
        local roll of its block-aligned slab and ``ppermute``s the
        boundary sets that cross shard edges under the global permutation
        (``mesh.make_sharded_roll``); no plane data touches the host.
        The ``"fanout"`` reference keeps the PR-4 host gather.
        Wear/replacement counters track PHYSICAL sets and are untouched.
        When admissions flow through an ``AdmitQueue``, the queue drains
        before calling this (drain barrier)."""
        n = self.cfg.n_sets
        shift = ROTATE_STRIDE % n
        self.offset = (self.offset + ROTATE_STRIDE) % n
        self.stats.rotations += 1
        if shift:
            if self.n_parts == 1:
                (self._bits[0], self._valid[0], self._fp_of[0],
                 self._read_after[0]) = _rotate_planes(
                    self._bits[0], self._valid[0], self._fp_of[0],
                    self._read_after[0], shift=shift)
            elif self._use_shard_map:
                self._rotate_device(shift)
            else:
                # "fanout" reference: cross-shard gather/scatter via the
                # global-view properties (getter concatenates, setter
                # re-splits and re-places per shard).
                self.bits = np.roll(self.bits, shift, axis=0)
                self.valid = np.roll(self.valid, shift, axis=0)
                self.fp_of = np.roll(self.fp_of, shift, axis=0)
                self.read_after = np.roll(self.read_after, shift, axis=0)
            self.valid_np = np.roll(self.valid_np, shift, axis=0)
            self.fp_of_np = np.roll(self.fp_of_np, shift, axis=0)
            self.slot_of = {fp: ((s + shift) % n, w)
                            for fp, (s, w) in self.slot_of.items()}

    def _rotate_device(self, shift: int):
        """On-device cross-shard remap: donated per-shard rolls + the
        ``ppermute`` boundary exchange, applied to all four planes in one
        jitted collective.  The assembled global views share buffers with
        the per-partition lists, so after the donation the lists are
        rebound from the outputs (zero-copy device views)."""
        roll = mesh_mod.make_sharded_roll(
            self.set_mesh, self.cfg.n_sets, shift)
        bits, valid, fp_of, read_after = roll(
            self._assemble(self._bits), self._assemble(self._valid),
            self._assemble(self._fp_of), self._assemble(self._read_after))
        self._bits = self._split_global(bits)
        self._valid = self._split_global(valid)
        self._fp_of = self._split_global(fp_of)
        self._read_after = self._split_global(read_after)

    # ------------------------------------------------------------------
    @property
    def hit_rate(self) -> float:
        t = self.stats.chunk_hits + self.stats.chunk_misses
        return self.stats.chunk_hits / max(t, 1)

    def slab_lockstep_report(self) -> dict:
        """Lockstep audit between the index and its attached slab store.

        Returns ``{"missing_slabs": [...], "orphan_slabs": [...]}`` —
        resident fingerprints without a slab (only possible when some
        admissions bypassed slab staging, e.g. plain ``admit()``) and
        slabs whose fingerprint the index no longer holds (a true
        lockstep violation: an evicted way must drop its slab).  Both
        empty when every admission staged a slab — tests assert exactly
        that, across rotation/eviction/async-drain schedules.
        """
        if self.slab_store is None:
            return {"missing_slabs": [], "orphan_slabs": []}
        indexed = {int(fp) for fp in self.slot_of}
        resident = self.slab_store.resident_fps()
        return {"missing_slabs": sorted(indexed - resident),
                "orphan_slabs": sorted(resident - indexed)}

    def write_distribution(self) -> np.ndarray:
        """Installs per PHYSICAL set — the wear-evenness metric (device
        counter; unlike residency it never decays on eviction).  Shape
        (n_sets,), concatenated in shard order."""
        return np.asarray(self.set_writes)

    def wear_report(self) -> dict:
        """Serving-side §8 wear stats from the shared WearState(s).

        Returns
        -------
        dict
            ``installs_per_set_max/mean``, ``skew_max_over_mean`` (wear
            evenness), ``window_writes`` (per-set, shard-concatenated),
            ``throttled_sets_now`` (sets an admission would be rejected
            from right now — the admit path rejects via
            ``window_would_exceed`` BEFORE the write, so
            ``record_write``'s post-overflow lock never engages here),
            plus the throttle/rotation stats.  Identical at every shard
            count for the same schedule.
        """
        w = self.write_distribution().astype(np.float64)
        mean = float(w.mean()) if w.size else 0.0
        cyc = jnp.asarray(min(self._clock_cycles(), 2 ** 31 - 1), jnp.int32)
        throttled_now = sum(
            int(np.asarray(wear.window_would_exceed(
                self._wear_states[k], self._wear_dyns[k],
                jnp.arange(self.sets_per_part), cyc)).sum())
            for k in range(self.n_parts))
        return {
            "installs_per_set_max": float(w.max()) if w.size else 0.0,
            "installs_per_set_mean": mean,
            "skew_max_over_mean": float(w.max() / mean) if mean > 0 else 1.0,
            "window_writes": np.asarray(
                self.wear_state.window_writes).tolist(),
            "throttled_sets_now": throttled_now,
            "throttled": self.stats.throttled,
            "rotations": self.stats.rotations,
        }

    def lifetime_estimate(self, endurance: float = 1e8,
                          ops_per_second: float = 1e6
                          ) -> lifetime_mod.LifetimeResult:
        """Fig. 11-style lifetime projection from the serving write
        snapshot — the same cumulative-crossing replay the simulator's
        curves use, fed by the device install counters."""
        return lifetime_mod.estimate_from_ops(
            self.write_distribution(), self.ops_total,
            self.stats.rotations, endurance=endurance,
            ops_per_second=ops_per_second)
