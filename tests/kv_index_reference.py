"""The reference the index's differential tests compare against.

``MonarchKVIndex`` admits a batch as a round grid
(``kv_index._admit_rounds_body``): each round is vectorized over
candidates of pairwise distinct sets.  :class:`ReferenceKVIndex` is the
same index at ``n_shards=1`` whose admission instead scans the
candidates one at a time, in batch order, over its one set of planes
(:func:`_admit_batch`) — the semantics the round grid must reproduce
bit for bit.  Sharding only relabels which device stores a set (the
invariance tests in ``tests/test_kv_index_sharded.py`` pin that), so
this one unsharded reference is the oracle at every shard count.  Its
lookup is the one-partition kernel (``xam_search_multiset``) over the
global planes; ``_shadow_hits`` (the host shadow map) checks both.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import wear
from repro.kernels.common import bucket_pow2
from repro.serve.kv_index import (ADMIT_BUCKET_LO, KVIndexConfig,
                                  MonarchKVIndex)


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


_COUNTERS = ("write_counter", "superset_counter", "dirty_counter")


class ReferenceKVIndex(MonarchKVIndex):
    """``MonarchKVIndex`` at ``n_shards=1`` admitting by the sequential
    scan.  Takes the config of the index under test, at any shard count,
    and the same keyword arguments."""

    def __init__(self, cfg: KVIndexConfig, **kw):
        super().__init__(dataclasses.replace(cfg, n_shards=1), **kw)

    def _admit_device(self, fps, sets, touches, bitcols, cycles):
        b = int(fps.size)
        bb = bucket_pow2(b, lo=ADMIT_BUCKET_LO)

        def pad(x, fill=0):
            out = np.full((bb,) + x.shape[1:], fill, x.dtype)
            out[:b] = x
            return out

        # The index keeps one entry per partition of each scalar wear
        # counter; the sequential scan takes them as scalars.
        ws = dataclasses.replace(
            self._wear, **{f: getattr(self._wear, f)[0] for f in _COUNTERS})
        carry, outs = _admit_batch(
            self.bits, self.valid, self.fp_of, self.read_after,
            self.set_writes, self.counter, ws, self.wear_dyn,
            self._admit_after, pad(sets), pad(fps), pad(bitcols),
            pad(cycles, cycles[0]), pad(touches), pad(np.ones(b, bool)))
        (self.bits, self.valid, self.fp_of, self.read_after,
         self.set_writes, self.counter, ws) = carry
        self._wear = dataclasses.replace(
            ws, **{f: getattr(ws, f)[None] for f in _COUNTERS})
        _res, skip, thr, inst, way, evict, old_fp = (
            np.asarray(o)[:b] for o in outs)
        return skip, thr, inst, way, evict, old_fp
