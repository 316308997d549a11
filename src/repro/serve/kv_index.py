"""MonarchKVIndex — the paper's technique as a first-class serving feature.

A vLLM-style paged KV prefix cache whose INDEX is a Monarch flat-CAM,
SHARDED along the set axis across a ``("sets",)`` device mesh
(``launch/mesh.make_set_mesh``).  The paper's headline win is in-package
parallelism — many XAM subarrays searched concurrently behind one wide
interface (§III) — and the set axis is exactly that parallelism at serving
scale.  Each CAM plane and counter is ONE array over all ``n_sets`` sets:
a plain device array on one device, a ``P("sets")`` array on a set mesh,
where mesh device k holds the contiguous block of physical sets
``[k * sets_per_part, (k + 1) * sets_per_part)`` (``geometry.
shard_of_set``): its stored-bit/validity/fingerprint planes, D̄&R̄
metadata, per-set replacement counters and §8 wear rows.

Data flow per batch:

* LOOKUP: every 16-token chunk is fingerprinted (murmur3) and the whole
  batch is answered by ONE device dispatch: the fused multiset kernel
  (``xam_ops.xam_search_multiset``) over the planes on one device, and on
  a set mesh its ``shard_map`` wrapping
  (``xam_ops.xam_search_multiset_stacked``: a stacked ``(n_parts, Qmax,
  R)`` layout of per-partition per-set blocks, Qmax pow2-bucketed,
  per-partition valid block counts scalar-prefetched), so XLA places all
  per-partition searches from a single call.
* ADMISSION: ONE device dispatch per batch as well.  The host packs
  candidates into the ROUND GRID of ``xam_ops.group_admits_stacked`` — a
  ``(n_parts, n_rounds, round_width)`` layout where round r holds each
  set's rank-r candidate (per-set prefix ranks; both axes pow2-bucketed)
  — and ``_admit_rounds_body``, jitted as is on one device and through
  ``shard_map`` on a set mesh, runs a ``lax.scan`` over rounds whose step
  admits a whole round VECTORIZED: residency probe, no-allocate gate,
  t_MWW throttle (``core/wear.py`` — the same machinery the Fig. 11
  simulator scans), cold-victim way selection, column install and
  vectorized wear recording (``wear.record_write_rows``).  Decisions
  couple only through per-set state (residency, window budget, the
  per-set replacement counter) and a round's sets are pairwise distinct
  by construction (same-set candidates differ in rank), so the
  round-parallel schedule is bit-equivalent to one sequential scan of
  the candidates in batch order.  ``tests/kv_index_reference.py`` keeps
  that scan as the reference the differential tests replay randomized
  schedules against, after every op, at ``n_shards in {1, 2, 4}``.
* ROTATION: the rotary remap is the GLOBAL permutation ``set -> set + 7``
  applied to the planes in lockstep with the ``_set_of`` offset bump, so
  resident entries stay searchable after the remap and the fingerprint
  -> physical-set mapping — hence wear accounting — is independent of
  the shard count.  On one device it is one donated roll; on a set mesh,
  per-partition rolls plus a ``ppermute`` boundary exchange of the sets
  that cross partition edges (``geometry.shard_roll_plan`` /
  ``mesh.make_sharded_roll``).  Plane data never moves through the host,
  and set_writes/wear track PHYSICAL sets so they never move at all.

The replacement counter is PER SET.  A global counter would couple victim
choice in one set to eviction traffic in every other set — the single
cross-set dependency that would make admission results depend on how
sets are sharded.  Per-set counters keep the §8 "random counter"
replacement flavor while making every partition's scan equal to the
global sequential order.

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
benchmarks/kernels_bench.py (``kv_index_admit`` and
``kv_index_admit_stacked`` time the batched admission,
``kv_index_lookup_sharded`` the lookup at four shards and
``kv_index_admit_async`` the queue overlap).  See docs/ARCHITECTURE.md
for the paper-concept -> code map and docs/SERVING.md for the operator
guide.
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
from repro.kernels.common import pack_bits_np, resolve_plane_format
from repro.kernels.xam_search import ops as xam_ops
from repro.launch import mesh as mesh_mod
from repro.serve.spans import span

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
    searches: int = 0             # lookup dispatches (1 per batch)
    admit_calls: int = 0          # admission dispatches (1 per batch)


def _admit_rounds_body(bits, valid, fp_of, read_after, set_writes, counter,
                       wstate, wdyn, admit_after, sets, fps, bitcols, cycles,
                       touches, active):
    """Segmented-parallel admission over the round grid (ONE partition).

    The state operands are one partition's blocks: the planes and per-set
    counters over its sets, and its wear state, whose three scalar
    counters arrive as ``(1,)`` arrays (one entry of the index's
    per-partition counters).  The candidate operands are that partition's
    ``(1, n_rounds, round_width)`` slice of the grid from
    ``xam_ops.group_admits_stacked``: round r holds each set's rank-r
    candidate, so within a round every active lane targets a DISTINCT
    set.  The ``lax.scan`` over rounds replays intra-set collisions in
    exact batch order (rank order IS batch order within a set) while each
    round's step runs the full per-fingerprint pipeline vectorized over
    the lanes — gathers row-clipped, installs scattered with an
    out-of-bounds sentinel so inactive / non-installing lanes write
    nothing, wear recorded via ``wear.record_write_rows`` (distinct rows
    per round is exactly its contract).  Because every decision couples
    only through per-set state, the result is bit-identical to a
    sequential scan of the candidates in batch order (the reference in
    ``tests/kv_index_reference.py``).  The decision grids come back with
    the same leading partition axis.
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

        # t_MWW lifetime throttle — shared wear machinery
        # (reject-before-write, per-set window).
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
    carry, outs = jax.lax.scan(
        round_step, carry,
        (sets[0], fps[0], bitcols[0], cycles[0], touches[0], active[0]))
    return carry, tuple(o[None] for o in outs)


def _wear_layout(rows, whole) -> wear.WearState:
    """Where each field of the index's wear state lives: the per-set rows
    and the per-partition scalar counters along the set axis (``rows``),
    the rotary offsets and rotate totals — which serving never changes,
    since its config disables every rotate signal — whole (``whole``).
    Filled with shardings to place the state, with partition specs to
    hand it to ``shard_map``."""
    return wear.WearState(
        swt_w=rows, swt_d=rows, write_counter=rows, superset_counter=rows,
        dirty_counter=rows, offsets=whole, window_writes=rows,
        window_start=rows, locked_until=rows, total_rotates=whole,
        total_flushed=whole)


@functools.lru_cache(maxsize=None)
def _admit_program(mesh):
    """The jitted admission dispatch: ``_admit_rounds_body`` as is when
    ``mesh`` is None, else through ``shard_map`` over the ``("sets",)``
    mesh, each device receiving its ``P("sets")`` blocks of the state and
    of the candidate grid while the wear knobs and the no-allocate
    threshold arrive replicated.  The planes, counters and wear state are
    donated."""
    body = _admit_rounds_body
    if mesh is not None:
        rows, whole = P("sets"), P()
        state = (rows,) * 6 + (_wear_layout(rows, whole),)
        body = jax.shard_map(
            body, mesh=mesh, in_specs=state + (whole, whole) + (rows,) * 6,
            out_specs=(state, rows), check_vma=False)
    return jax.jit(body, donate_argnums=tuple(range(7)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("shift",))
def _rotate_planes(bits, valid, fp_of, read_after, shift: int):
    """Device start-gap-style remap: cyclically shift every set plane by the
    prime stride — resident entries move WITH the ``_set_of`` offset bump,
    so they stay searchable under the rotated mapping.  No host rebuild."""
    roll = lambda x: jnp.roll(x, shift, axis=0)
    return roll(bits), roll(valid), roll(fp_of), roll(read_after)


def _set_bytes(plane) -> int:
    """Bytes one set of an ``(n_sets, ...)`` device plane holds."""
    return int(np.prod(plane.shape[1:])) * np.dtype(plane.dtype).itemsize


class KVSlabStore:
    """KV slab store kept in LOCKSTEP with the index.

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

    Residency: a slab whose leaves are device arrays (what the resume
    engine stages) stays on the device while the resident device bytes
    fit ``device_budget``; a commit beyond it copies that slab to host
    memory, outside the store lock, and swaps the copy in (``spilled``
    counts these).  The slab is servable throughout.  By default the
    budget is derived from the device's own ``memory_stats()``: its
    ``bytes_limit``, less a sixteenth of it for fragmentation, less the
    largest device footprint the process has reached apart from resident
    slabs (``peak_bytes_in_use`` read at every commit, minus the
    resident device bytes when it was read), so that serving's own
    transients, as large as any seen so far, still fit beside the slabs.
    A backend without memory stats (the CPU) leaves the tier unbounded;
    ``device_budget`` (bytes) fixes it instead, for tests.

    Thread safety: all methods take the store lock; staging (serving
    thread, right after prefill) may race commits (AdmitQueue worker).
    The lock is reentrant, so :meth:`get_many` reads through :meth:`get`
    under one hold of it.

    A slab is an arbitrary pytree (per-layer k/v arrays for one chunk);
    the store never inspects it beyond byte accounting and residency.
    """

    def __init__(self, device_budget: int | None = None):
        self._lock = threading.RLock()
        self._staged: dict[int, object] = {}
        # fp -> (slab, bytes it takes, whether they are device bytes)
        self._resident: dict[int, tuple[object, int, bool]] = {}
        self._fixed_budget = device_budget
        self._peak_seen = 0          # peak_bytes_in_use at the last read
        self._other_peak = 0         # largest device footprint apart from slabs
        self.device_bytes = 0        # device memory of resident slabs
        self.host_bytes = 0          # host memory of resident slabs
        self.spilled = 0             # commits moved to host memory

    @staticmethod
    def _nbytes(slab) -> int:
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree.leaves(slab))

    @staticmethod
    def _device_of(slab):
        """The device holding ``slab``'s first device leaf, or None for a
        host slab."""
        for leaf in jax.tree.leaves(slab):
            if isinstance(leaf, jax.Array):
                return min(leaf.devices(), key=lambda d: d.id)
        return None

    def _budget(self, device) -> float:
        """Resident device bytes ``device`` may hold (store lock held)."""
        if self._fixed_budget is not None:
            return self._fixed_budget
        stats = device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return float("inf")
        peak = int(stats.get("peak_bytes_in_use", 0))
        if peak > self._peak_seen:
            # The new peak was reached while the resident device bytes
            # were at most what they are now (each commit reads it first).
            self._peak_seen = peak
            self._other_peak = max(self._other_peak,
                                   peak - self.device_bytes)
        limit = int(stats["bytes_limit"])
        return limit - limit // 16 - self._other_peak

    def _hold(self, fp: int, slab, on_device: bool) -> None:
        self._release(fp)
        # A device slab is counted as laid out on the device (its tiles
        # padded), which is what the budget and memory_stats() see.
        n = (sum(leaf.on_device_size_in_bytes()
                 for leaf in jax.tree.leaves(slab)) if on_device
             else self._nbytes(slab))
        self._resident[fp] = (slab, n, on_device)
        if on_device:
            self.device_bytes += n
        else:
            self.host_bytes += n

    def _release(self, fp: int) -> None:
        _, n, on_device = self._resident.pop(fp, (None, 0, False))
        if on_device:
            self.device_bytes -= n
        else:
            self.host_bytes -= n

    def stage(self, fp: int, slab) -> None:
        """Hold a freshly computed slab until its admission decides."""
        with self._lock:
            self._staged[int(fp)] = slab

    def commit(self, fp: int) -> None:
        """Fingerprint installed (or re-offered while resident): promote
        its staged slab, to host memory if the device tier is full.
        No-op when nothing is staged (e.g. a resident refresh admitted
        via the slab-less ``admit()`` path)."""
        fp = int(fp)
        with self._lock:
            slab = self._staged.pop(fp, None)
            if slab is None:
                return
            device = self._device_of(slab)
            budget = float("inf") if device is None else self._budget(device)
            self._hold(fp, slab, device is not None)
            spill = self.device_bytes > budget
        if spill:
            host = jax.tree.map(np.asarray, slab)
            with self._lock:
                # unless dropped or replaced meanwhile
                if self._resident.get(fp, (None,))[0] is slab:
                    self._hold(fp, host, False)
                    self.spilled += 1

    def discard(self, fp: int) -> None:
        """Offer skipped/throttled/shed: the staged slab is garbage."""
        with self._lock:
            self._staged.pop(int(fp), None)

    def drop(self, fp: int) -> None:
        """Fingerprint evicted from its way: the resident slab dies with
        it (the lockstep half of the index's eviction)."""
        with self._lock:
            self._release(int(fp))

    def get(self, fp: int):
        """Resident slab for ``fp``, or None (staged slabs are NOT
        servable — their admission has not happened yet)."""
        with self._lock:
            return self._resident.get(int(fp), (None,))[0]

    def get_many(self, fps) -> list:
        """Resident slabs for ``fps`` (None where absent), read under one
        hold of the store lock, so no commit or drop lands between them."""
        with self._lock:
            return [self.get(fp) for fp in fps]

    def resident_fps(self) -> set[int]:
        with self._lock:
            return set(self._resident)

    def resident_slabs(self) -> list:
        """Every resident slab, read under one hold of the store lock."""
        with self._lock:
            return [slab for slab, _, _ in self._resident.values()]

    def staged_fps(self) -> set[int]:
        with self._lock:
            return set(self._staged)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self.device_bytes + self.host_bytes

    def stats(self) -> dict:
        """Residency counters, for ``GET /stats``."""
        with self._lock:
            return {"resident": len(self._resident),
                    "device_bytes": self.device_bytes,
                    "host_bytes": self.host_bytes,
                    "spilled": self.spilled}


class MonarchKVIndex:
    """Set-sharded Monarch flat-CAM prefix index (see module docstring).

    Parameters
    ----------
    cfg : KVIndexConfig, optional
        Geometry/durability knobs; default-constructed per instance.
    seed : int
        Reserved for future stochastic policies (placement is currently
        deterministic).
    now_fn : callable, optional
        Wall-clock source for ``clock="wall"`` configs: a zero-arg
        callable returning MONOTONIC seconds as a float (default
        ``time.monotonic``).  Injectable so tests drive the latency-era
        t_MWW window deterministically.  Never consulted under
        ``clock="ops"`` (pinned — the op-clock path is bit-identical to
        the pre-wall-clock implementation).
    slab_store : KVSlabStore, optional
        KV slab store kept in lockstep with admission (commit on install,
        discard on skip/throttle, drop on evict); None = tag-only index
        (dedup, counting).

    Attributes
    ----------
    bits, valid, fp_of, read_after : jax.Array
        The CAM planes — ``(n_sets, key_bits, set_ways)`` int8 stored
        bits (``(n_sets, key_bits // 8, set_ways)`` uint8 packed words
        under ``plane_format="packed8"`` — unpack with
        ``kernels.common.unpack_bits_np(..., axis=1)``),
        ``(n_sets, set_ways)`` validity/fingerprint/D̄&R̄ planes.  Sharded
        ``P("sets")`` over ``set_mesh`` when there is one.  Admission and
        rotation donate them and rebind the attributes, so read them
        afresh after either.
    set_writes, counter : jax.Array
        ``(n_sets,)`` per-set install (wear) and replacement counters,
        laid out like the planes.
    set_mesh : Mesh or None
        The ``("sets",)`` mesh the state is sharded over; None when the
        host has one device (every shard co-locates).
    n_parts : int
        Device partitions holding state: the ``set_mesh`` size, or 1.
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
                 now_fn=None, slab_store: KVSlabStore | None = None):
        # cfg default constructed per instance: a shared KVIndexConfig()
        # default would alias mutable config across indexes.
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
        self.slab_store = slab_store
        # t_MWW clock domain.  "ops": the op counter is the cycle proxy
        # (pre-existing semantics, now_fn never consulted).  "wall": cycle
        # stamps are host wall microseconds relative to construction,
        # taken ONCE per admission batch so the device scans see only
        # host-provided constants and stay deterministic.
        self.clock = c.clock
        self._now_fn = time.monotonic if now_fn is None else now_fn
        self._wall_t0 = self._now_fn() if self.clock == "wall" else 0.0
        self._wall_folded = 0       # cycles removed by clock rebases
        self.sets_per_shard = geometry.sets_per_shard(c.n_sets, c.n_shards)
        # State is stored in one block per MESH PARTITION (sharding is a
        # pure relabeling, so coarsening co-located shards into one block
        # changes no result — pinned by the invariance tests); on a
        # one-device host that is the plain unsharded layout.
        self.set_mesh = mesh_mod.make_set_mesh(c.n_shards)
        if self.set_mesh is None:
            self.n_parts = 1
            rows = whole = jax.devices()[0]
        else:
            self.n_parts = int(self.set_mesh.devices.size)
            rows = mesh_mod.set_axis_sharding(self.set_mesh)
            whole = mesh_mod.replicated_sharding(self.set_mesh)
        self._rows = rows           # placement of per-set state and grids
        self.sets_per_part = c.n_sets // self.n_parts
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
        # Device-resident CAM state: fingerprint bits column-wise per set,
        # plus the validity / fingerprint / D-R metadata planes, the
        # PER-SET replacement counters and the per-set install (wear)
        # counters.
        ways = (c.n_sets, c.set_ways)
        place = functools.partial(jax.device_put, device=rows)
        self.bits = place(np.zeros((c.n_sets, self.plane_rows, c.set_ways),
                                   plane_dtype))
        self.valid = place(np.zeros(ways, np.int8))
        self.fp_of = place(np.zeros(ways, np.uint32))
        self.read_after = place(np.zeros(ways, np.int32))
        self.set_writes = place(np.zeros(c.n_sets, np.int32))
        self.counter = place(np.zeros(c.n_sets, np.int32))
        # §8 wear state over the physical sets — the simulator's own
        # machinery with serving knobs: window length = window_ops (op-count
        # cycle proxy), budget = set_ways * m_writes, WR/WC/DC rotation
        # signals disabled (serving rotates on the rotate_every cadence).
        # wr_shift=32 actually disables WR — int32 MSB distances never
        # reach 32, so ``rotate_signal`` provably never fires (the default
        # shift of 9 left WR armed despite the stated intent).  That
        # invariance is also what makes the vectorized wear recording of
        # the round-grid admission exact (``wear.record_write_rows``).
        # Its three scalar counters are kept per partition, one entry
        # each (``wear_state`` sums them).
        self.wear_cfg = wear.WearConfig(
            n_supersets=c.n_sets, m_writes=c.m_writes,
            dc_limit=1 << 30, wc_limit=1 << 30, wr_shift=32,
            t_mww_cycles=c.window_ops, blocks_per_superset=c.set_ways,
            clock=c.clock)
        per_part = np.zeros(self.n_parts, np.int32)
        self._wear = jax.device_put(
            dataclasses.replace(
                wear.init_state(self.wear_cfg), write_counter=per_part,
                superset_counter=per_part, dirty_counter=per_part),
            _wear_layout(rows, whole))
        # Placed once at construction, so the per-batch admission
        # dispatch performs no implicit host transfers.
        self.wear_dyn = jax.device_put(wear.dyn_of(self.wear_cfg), whole)
        self._admit_after = jax.device_put(
            np.asarray(c.admit_after_reads, np.int32), whole)
        # Host-side policy shadow (map + mirrors): keeps assertions and
        # eviction bookkeeping off the device sync path.
        self.valid_np = np.zeros((c.n_sets, c.set_ways), bool)
        self.fp_of_np = np.zeros((c.n_sets, c.set_ways), np.uint32)
        self.slot_of = {}           # fp -> (set, way) (host-side shadow map)
        self.first_touch = {}       # fp -> touch count (pre-admission)
        self.offset = 0             # rotary set offset
        self.ops_total = 0          # op counter == t_MWW cycle proxy
        self.stats = KVIndexStats()

    @property
    def wear_state(self) -> wear.WearState:
        """Global §8 wear view: the per-set rows over every set and the
        scalar counters summed over partitions — reporting only, never
        write through."""
        w = self._wear
        return dataclasses.replace(
            w, write_counter=jnp.sum(w.write_counter),
            superset_counter=jnp.sum(w.superset_counter),
            dirty_counter=jnp.sum(w.dirty_counter))

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
        self._wear = wear.rebase_clock(self._wear, wear.CLOCK_REBASE_AT)
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
            dispatch for the whole batch: the fused multiset kernel on
            one device, its ``shard_map`` wrapping over the ``("sets",)``
            mesh (the stacked layout) otherwise.
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
        with span("lookup.search", queries=key_bits.shape[0],
                  key_bits=key_bits.shape[1], ways=self.bits.shape[-1],
                  sets=np.unique(sets).size,
                  set_bytes=_set_bytes(self.bits) + _set_bytes(self.valid)):
            if self.set_mesh is None:
                ways = xam_ops.xam_search_multiset(
                    key_bits, sets, self.bits, self.valid)
            else:
                ways = xam_ops.xam_search_multiset_stacked(
                    key_bits, sets, self.bits, self.valid,
                    mesh=self.set_mesh)
        self.stats.searches += 1
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
        :meth:`admit_fps` — one device dispatch regardless of batch
        size."""
        fps = np.unique(self.fingerprints(tokens).reshape(-1))
        self.admit_fps(fps)

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
        The whole batch is admitted by ONE donated device dispatch at
        every shard count (:meth:`_admit_device`).  Because every
        decision couples only through per-set state, the result is
        bit-equivalent to admitting the same fingerprints one at a time
        in batch order, at any shard count (and any partitioning of
        shards onto devices).
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
        # t_MWW cycle stamps, computed ONCE here on the host.  Op clock:
        # each candidate's global batch position.  Wall clock: one host
        # timestamp for the whole batch — the device scan sees only host
        # constants either way, so it stays deterministic.
        if self.clock == "ops":
            cycles = (self.ops_total + np.arange(b)).astype(np.int32)
        else:
            cycles = np.full(b, self._clock_cycles(), np.int32)
        skip, thr, inst, way, evict, old_fp = self._admit_device(
            fps, sets, touches, bitcols, cycles)
        self.ops_total += b

        # Host shadow-map fold, in batch order.  The slab store folds in
        # lockstep: a victim's slab dies with its way, a staged slab
        # becomes resident exactly when its fingerprint installs (or
        # refreshes a resident way), and is discarded on skip/throttle so
        # rejected KV never serves a hit.
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

    def _admit_device(self, fps, sets, touches, bitcols, cycles):
        """ONE-dispatch admission over the round grid.

        Packs the batch into the ``(n_parts, n_rounds, round_width)``
        grid of ``xam_ops.group_admits_stacked`` (pow2-bucketed on both
        candidate axes so repeated batch sizes reuse compilations), places
        it with explicit ``device_put`` calls (so the dispatch stays legal
        under ``jax.transfer_guard("disallow")``), and launches the
        single donated ``_admit_program`` call.  Returns the
        per-candidate decision arrays in batch order."""
        c = self.cfg
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
        put = functools.partial(jax.device_put, device=self._rows)
        state, outs = _admit_program(self.set_mesh)(
            self.bits, self.valid, self.fp_of, self.read_after,
            self.set_writes, self.counter, self._wear, self.wear_dyn,
            self._admit_after, put(sets_g), put(fps_g), put(bit_g),
            put(cyc_g), put(tch_g), put(act_g))
        (self.bits, self.valid, self.fp_of, self.read_after,
         self.set_writes, self.counter, self._wear) = state

        # One sync for the whole batch; un-grid back to batch order.
        _res, skip, thr, inst, way, evict, old_fp = (
            np.asarray(o)[idx] for o in jax.device_get(outs))
        return skip, thr, inst, way, evict, old_fp

    def _rotate(self):
        """Rotary remap (prime stride 7): shift the set planes by the
        GLOBAL permutation ``set -> set + 7 (mod n_sets)`` while the
        ``_set_of`` offset moves in lockstep, so resident entries stay
        searchable under the rotated placement and the physical mapping is
        identical at every shard count.  One device: ONE donated device
        roll.  On a set mesh: each partition donates a local roll of its
        block and ``ppermute``s the boundary sets that cross partition
        edges (``mesh.make_sharded_roll``); no plane data touches the
        host.  Wear/replacement counters track PHYSICAL sets and are
        untouched.  When admissions flow through an ``AdmitQueue``, the
        queue drains before calling this (drain barrier)."""
        n = self.cfg.n_sets
        shift = ROTATE_STRIDE % n
        self.offset = (self.offset + ROTATE_STRIDE) % n
        self.stats.rotations += 1
        if shift:
            roll = (functools.partial(_rotate_planes, shift=shift)
                    if self.set_mesh is None
                    else mesh_mod.make_sharded_roll(self.set_mesh, n, shift))
            self.bits, self.valid, self.fp_of, self.read_after = roll(
                self.bits, self.valid, self.fp_of, self.read_after)
            self.valid_np = np.roll(self.valid_np, shift, axis=0)
            self.fp_of_np = np.roll(self.fp_of_np, shift, axis=0)
            self.slot_of = {fp: ((s + shift) % n, w)
                            for fp, (s, w) in self.slot_of.items()}

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
        (n_sets,)."""
        return np.asarray(self.set_writes)

    def wear_report(self) -> dict:
        """Serving-side §8 wear stats from the shared WearState.

        Returns
        -------
        dict
            ``installs_per_set_max/mean``, ``skew_max_over_mean`` (wear
            evenness), ``window_writes`` (per-set), ``throttled_sets_now``
            (sets an admission would be rejected from right now — the
            admit path rejects via ``window_would_exceed`` BEFORE the
            write, so ``record_write``'s post-overflow lock never engages
            here), plus the throttle/rotation stats.  Identical at every
            shard count for the same schedule.
        """
        w = self.write_distribution().astype(np.float64)
        mean = float(w.mean()) if w.size else 0.0
        cyc = jnp.asarray(min(self._clock_cycles(), 2 ** 31 - 1), jnp.int32)
        throttled_now = int(np.asarray(wear.window_would_exceed(
            self._wear, self.wear_dyn, jnp.arange(self.cfg.n_sets),
            cyc)).sum())
        return {
            "installs_per_set_max": float(w.max()) if w.size else 0.0,
            "installs_per_set_mean": mean,
            "skew_max_over_mean": float(w.max() / mean) if mean > 0 else 1.0,
            "window_writes": np.asarray(self._wear.window_writes).tolist(),
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
