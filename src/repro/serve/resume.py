"""Prefix-cache resume engine: restore cached KV slabs, prefill the
suffix from its RoPE offset, decode from the combined cache.

This is the consumer side of the Monarch prefix index — the piece that
turns an index HIT into saved prefill compute.  The flow per request
batch (driven by ``launch/serve.py::run_request_loop``):

1. ``lookup`` (through the AdmitQueue) answers which leading chunks of
   the prompt are cached — ONE fused XAM search for the whole batch.
2. :meth:`PrefixResumeEngine.prefill` fetches the hit chunks' KV slabs
   from the index's :class:`~repro.serve.kv_index.KVSlabStore` in one
   pass, joins them into a ``prefix_kv`` pytree with jitted
   concatenations on the device (each row's run, then the rows), and
   runs
   ``transformer.prefill(prefix_kv=...)`` over ONLY the suffix tokens —
   suffix positions start at the prefix length (the RoPE offset
   contract: resumed tokens attend at their original absolute
   positions), so the resulting cache and logits are bit-identical to a
   full prefill of the whole prompt.
3. The chunks it DID compute are cut into per-chunk slabs by one jitted
   split (device arrays: slab bytes stay on the chip from the moment
   they are cut) and handed back (:class:`PrefillResult`), which the
   request loop stages via
   ``AdmitQueue.submit_tokens(toks, slabs=...)`` — submit-after-prefill,
   so the async admission worker commits slabs while decode runs.
4. :meth:`PrefixResumeEngine.decode` greedily decodes from the restored
   cache, positions continuing at the full prompt length.  Each step is
   dispatched before the host reads the token it consumes, so the
   device always has the next step queued while the host reads; the
   last token is read with no step after it (n-1 steps for n tokens).

Correctness ground rules (all pinned by ``tests/test_decode_resume.py``):

* The index MUST hash with ``fingerprint="prefix"`` (chained chunk
  hashes): a chunk's KV depends on its entire prefix, so content-equal
  chunks with different prefixes must not share slabs.
* At least the last prompt token is always recomputed (``run`` is capped
  at ``(S-1) // CHUNK_TOKENS`` chunks) — a fully-cached prompt still
  needs last-token logits to seed decode.
* A hit whose slab is missing (admitted slab-less, or shed/evicted
  between lookup and fetch) truncates the resume run — graceful
  recompute, never a wrong answer.
* Recurrent layers fold the whole prefix into one state, which no KV
  slab holds.  Mamba-2 hybrids (``transformer.resume_supported``)
  resume from a state snapshot instead: a prefill that computes the
  token at :func:`snapshot_token` hands back the Mamba-2 layers' fp32
  state and conv tail there, and the snapshot rides in the slab of the
  chunk ending at that token (``{"kv": ..., "state": ...}``; the other
  chunks' slabs carry ``"state": None``), so eviction drops the KV and
  the state together and the store's budget counts both.  A resume run
  is cut back to the deepest chunk whose slab holds a snapshot in every
  row; with none in reach the batch takes a full prefill.  Other
  recurrent layers (Mamba-1, zamba2's shared block) are refused.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.serve.kv_index import CHUNK_TOKENS, MonarchKVIndex
from repro.serve.spans import span
from repro.serve.step import make_decode_step, make_resume_prefill_step


def snapshot_token(cfg: ArchConfig, s: int) -> int:
    """Where a prompt of ``s`` tokens takes its state snapshot: the
    deepest multiple of ``cfg.ssm_chunk`` at or below the resumable cap
    of ``(s-1) // CHUNK_TOKENS`` chunks (0: no snapshot)."""
    cap = max(s - 1, 0) // CHUNK_TOKENS * CHUNK_TOKENS
    return cap // cfg.ssm_chunk * cfg.ssm_chunk


@dataclasses.dataclass
class PrefillResult:
    """What a resume-aware ``prefill_fn`` returns to the request loop.

    ``state`` is the opaque decode state (logits/cache/position) for
    ``decode_fn``, which adds ``first_token_at``: the ``time.monotonic()``
    stamp of the first decoded token's host read; ``slabs`` maps chunk
    fingerprints to freshly computed KV slabs for the loop to stage at
    submit time; the chunk counters feed the per-request records and the
    bench's resumed-fraction metric."""
    state: Any
    slabs: dict | None = None
    resumed_chunks: int = 0
    computed_chunks: int = 0


# Slab/kv pytree axis conventions: every leaf is (..., B, S, KV, dh) —
# the sequence axis is third-from-last, the batch axis fourth-from-last
# (scanned group leaves carry a leading (G,) axis, remainder leaves do
# not, so axes are addressed from the right).  The functions below run
# jitted on the device: slab bytes never pass through the host on the
# serving path.  A restore joins each row's run, then the rows: programs
# keyed by the run length and by the row count apart, not by both, since
# a program's compile time grows with its number of slab arguments.

def split_slabs(kv):
    """A batch's kv pytree -> per row, a tuple of its whole
    ``CHUNK_TOKENS`` chunks, each a slab with leaves (..., 1,
    CHUNK_TOKENS, KV, dh); a trailing partial chunk is left out."""
    leaf = jax.tree.leaves(kv)[0]
    rows, n = leaf.shape[-4], leaf.shape[-3] // CHUNK_TOKENS

    def chunk(a, r, c):
        return a[..., r:r + 1, c * CHUNK_TOKENS:(c + 1) * CHUNK_TOKENS,
                 :, :]
    return tuple(tuple(jax.tree.map(lambda a: chunk(a, r, c), kv)
                       for c in range(n)) for r in range(rows))


def join_run(slabs):
    """One row's run of chunk slabs -> its prefix kv pytree,
    concatenated along the sequence axis."""
    return jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=xs[0].ndim - 3), *slabs)


def join_rows(rows):
    """Per-row prefix kv pytrees -> the batch's, concatenated along the
    batch axis."""
    return jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=xs[0].ndim - 4), *rows)


# A state pytree (``prefill(snapshot_at=...)``) mirrors the cache: its
# leaves are (B, ...) at remainder layers and (G, B, ...) under "groups".

def _batch_axis(path) -> int:
    return 1 if getattr(path[0], "key", None) == "groups" else 0


def split_state(state):
    """A batch's state snapshot -> per row, a snapshot of batch 1."""
    path, leaf = jax.tree_util.tree_leaves_with_path(state)[0]
    rows = leaf.shape[_batch_axis(path)]
    return tuple(jax.tree_util.tree_map_with_path(
        lambda path, a: jax.lax.slice_in_dim(a, r, r + 1,
                                             axis=_batch_axis(path)),
        state) for r in range(rows))


def join_state(rows):
    """Per-row state snapshots -> the batch's."""
    return jax.tree_util.tree_map_with_path(
        lambda path, *xs: jnp.concatenate(xs, axis=_batch_axis(path)),
        *rows)


def _nbytes(tree, device_only: bool = False) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree)
               if not device_only or isinstance(a, jax.Array))


class PrefixResumeEngine:
    """Prefill/decode pair that serves prefix-cache hits from KV slabs.

    Parameters
    ----------
    params : pytree
        Model parameters (already placed on the serving mesh).
    cfg : ArchConfig
        Attention-only, or a Mamba-2 hybrid
        (``transformer.resume_supported``).
    max_seq : int
        Decode-cache capacity; prompts + decode tokens must fit.
    index : MonarchKVIndex
        Supplies the fingerprint scheme (must be ``"prefix"``) and the
        attached :class:`KVSlabStore` the engine fetches slabs from.
        The engine never mutates the index — lookups and admissions stay
        with the request loop / AdmitQueue.
    decode_tokens : int
        Default greedy-decode length for :meth:`decode`.
    jit : bool
        jit the prefill/decode steps and the slab split/join (on by
        default; off for debugging).
    """

    def __init__(self, params, cfg: ArchConfig, *, max_seq: int,
                 index: MonarchKVIndex, decode_tokens: int = 8,
                 jit: bool = True):
        blocker = transformer.resume_blocker(cfg)
        if blocker is not None:
            raise NotImplementedError(
                f"prefix resume cannot serve {cfg.name}: {blocker}")
        self.recurrent = transformer.has_recurrent_state(cfg)
        if self.recurrent and cfg.ssm_chunk % CHUNK_TOKENS:
            raise ValueError(
                f"{cfg.name}: ssm_chunk {cfg.ssm_chunk} is not a multiple "
                f"of CHUNK_TOKENS {CHUNK_TOKENS}, so no chunk slab ends at "
                "a state snapshot")
        if index.cfg.fingerprint != "prefix":
            raise ValueError(
                "PrefixResumeEngine needs KVIndexConfig(fingerprint="
                "'prefix'): per-chunk-independent fingerprints would let "
                "content-equal chunks with different prefixes share KV")
        if index.slab_store is None:
            raise ValueError(
                "PrefixResumeEngine needs an index with an attached "
                "KVSlabStore (MonarchKVIndex(..., slab_store=...))")
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.index = index
        self.store = index.slab_store
        self.decode_tokens = decode_tokens
        fn = make_resume_prefill_step(cfg, max_seq)
        self._prefill = (jax.jit(fn, static_argnames="snapshot_at") if jit
                         else fn)
        dec = make_decode_step(cfg)
        self._decode = jax.jit(dec) if jit else dec
        self._join_run = jax.jit(join_run) if jit else join_run
        self._join_rows = jax.jit(join_rows) if jit else join_rows
        self._split = jax.jit(split_slabs) if jit else split_slabs
        self._split_state = jax.jit(split_state) if jit else split_state
        self._join_state = jax.jit(join_state) if jit else join_state
        self.resumed_chunks = 0          # served from slabs, cumulative
        self.computed_chunks = 0         # recomputed, cumulative
        self.snapshots_staged = 0        # state snapshots offered
        self.snapshot_misses = 0         # rows cut back to a snapshot

    # ------------------------------------------------------------------
    def _resume_slabs(self, fps: np.ndarray, hits: np.ndarray,
                      s: int) -> tuple[list[list], list[list]]:
        """Per row, the slabs of the longest leading run of chunks
        servable for EVERY row: the chunk hit in the index AND its slab
        resident, fetched in one pass over the store.  Capped at
        ``(s-1) // CHUNK_TOKENS`` so at least one suffix token is always
        recomputed (last-token logits seed decode) — for chunk-aligned
        prompts that forces the last chunk out of the run; a partial
        trailing chunk is recomputed anyway and lifts the cap.  A
        recurrent arch cuts the run back to the deepest chunk whose slab
        holds a state snapshot in every row.  Also returns every slab
        fetched, per row."""
        b = fps.shape[0]
        cap = max(s - 1, 0) // CHUNK_TOKENS
        # leading hits of each row: the index of its first miss
        lead = min(int(np.argmin(np.append(hits[r, :cap], False)))
                   for r in range(b))
        got = self.store.get_many(fps[:, :lead].reshape(-1))
        rows = [got[r * lead:(r + 1) * lead] for r in range(b)]
        run = min(next((k for k, g in enumerate(row) if g is None), lead)
                  for row in rows)
        if self.recurrent:
            held = run
            while run and any(row[run - 1]["state"] is None for row in rows):
                run -= 1
            if run < held:
                self.snapshot_misses += b
        return [row[:run] for row in rows], rows

    def _kv(self, slab):
        return slab["kv"] if self.recurrent else slab

    def _restore(self, prefix: list[list]):
        """The batch's (prefix kv, prefix state) from its rows' slab runs,
        joined on the device, with the restore span's byte counts."""
        b = len(prefix)
        with span("resume.restore", rows=b) as restore:
            kv = [[self._kv(slab) for slab in row] for row in prefix]
            state = ([row[-1]["state"] for row in prefix] if self.recurrent
                     else None)
            restore.set_metadata(
                nbytes=_nbytes((kv, state)),
                device_nbytes=_nbytes((kv, state), device_only=True),
                state_nbytes=_nbytes(state))
            prefix_kv = [self._join_run(row) for row in kv]
            prefix_kv = (prefix_kv[0] if b == 1
                         else self._join_rows(prefix_kv))
            if state is not None:
                state = state[0] if b == 1 else self._join_state(state)
        return prefix_kv, state

    def prefill(self, toks: np.ndarray, hits=None) -> PrefillResult:
        """Restore + partial prefill of one request batch.

        ``hits`` is the request loop's lookup answer ((B, n_chunks)
        bool); ``None`` disables resume (full prefill — the no-cache
        baseline path, still returning slabs for admission)."""
        toks = np.asarray(toks, np.int32)
        b, s = toks.shape
        n_chunks = s // CHUNK_TOKENS
        with span("resume.prefill", rows=b) as outer:
            with span("resume.match"):
                fps = self.index.fingerprints(toks)
                if hits is None:
                    hits = np.zeros((b, n_chunks), bool)
                prefix, fetched = self._resume_slabs(
                    fps, np.asarray(hits, bool), s)
            run = len(prefix[0])
            p_len = run * CHUNK_TOKENS
            outer.set_metadata(prefix=p_len, suffix=s - p_len)
            # A recurrent arch snapshots its state at snapshot_token,
            # unless the resumed prefix already reaches it.
            snap_t = snapshot_token(self.cfg, s) if self.recurrent else 0
            kw = {"snapshot_at": snap_t} if snap_t > p_len else {}
            if run > 0:
                # Device slabs join on the device; a slab held in host
                # memory is uploaded by itself as an argument of the join.
                prefix_kv, prefix_state = self._restore(prefix)
                if self.recurrent:
                    kw["prefix_state"] = prefix_state
                with span("resume.step"):
                    out = self._prefill(
                        self.params, {"tokens": toks[:, p_len:]}, prefix_kv,
                        **kw)
            else:
                with span("resume.step"):
                    out = self._prefill(self.params, {"tokens": toks}, **kw)
            logits, cache, kv_suffix = out[:3]
            # Cut the freshly computed whole chunks into slabs to stage,
            # on the device; the first row holding a fingerprint gives it.
            with span("resume.slice") as sliced:
                slabs: dict[int, Any] = {}
                if n_chunks > run:
                    pieces = self._split(kv_suffix)
                    for r in range(b):
                        for c in range(run, n_chunks):
                            slabs.setdefault(int(fps[r, c]),
                                             pieces[r][c - run])
                sliced.set_metadata(nbytes=sum(map(_nbytes,
                                                   slabs.values())))
            if self.recurrent:
                self._attach_states(slabs, fps, fetched, run, out, snap_t)
        self.resumed_chunks += run * b
        self.computed_chunks += (n_chunks - run) * b
        state = {"logits": logits, "cache": cache, "pos": s}
        return PrefillResult(state=state, slabs=slabs,
                             resumed_chunks=run * b,
                             computed_chunks=(n_chunks - run) * b)

    def _attach_states(self, slabs: dict, fps, fetched, run: int, out,
                       snap_t: int) -> None:
        """Give a recurrent arch's slabs their ``"state"``: each row's
        snapshot (when this prefill took one) in the slab of the chunk
        ending at ``snap_t``, None elsewhere.  A slab without a snapshot
        never replaces a resident one holding a snapshot (the KV is the
        same): its chunk is offered without a slab."""
        snaps = {}
        if len(out) > 3:
            with span("resume.snapshot", rows=len(fps)) as sp:
                c = snap_t // CHUNK_TOKENS - 1
                for r, st in enumerate(self._split_state(out[3])):
                    snaps.setdefault(int(fps[r, c]), st)
                sp.set_metadata(nbytes=sum(map(_nbytes, snaps.values())))
            self.snapshots_staged += len(snaps)
        held = {int(fps[r, c]): slab
                for r, row in enumerate(fetched)
                for c, slab in enumerate(row) if c >= run}
        for fp in list(slabs):
            state = snaps.get(fp)
            old = held.get(fp)
            if state is None and old is not None and old["state"] is not None:
                del slabs[fp]
            else:
                slabs[fp] = {"kv": slabs[fp], "state": state}

    def stats(self) -> dict:
        """Snapshot counters, for ``GET /stats``: snapshots staged (one a
        prompt that computes its snapshot token), resident with their
        state bytes, and ``snapshot_misses``, rows whose resident hit run
        was cut short or dropped for want of a snapshot."""
        resident = ([slab["state"] for slab in self.store.resident_slabs()
                     if slab["state"] is not None] if self.recurrent else [])
        return {"snapshots_staged": self.snapshots_staged,
                "snapshots_resident": len(resident),
                "snapshot_bytes": sum(map(_nbytes, resident)),
                "snapshot_misses": self.snapshot_misses}

    def decode(self, result, n_tokens: int | None = None) -> np.ndarray:
        """Greedy decode from a :meth:`prefill` result (or its bare
        ``state``).  Returns the (B, n_tokens) decoded ids; positions
        continue at the full prompt length regardless of how much
        prefill was skipped.  Stamps ``state["first_token_at"]`` when the
        first token reaches the host.

        Step t (token t in, token t+1 out) is dispatched before token t
        is read, so the host's read and its next dispatch overlap the
        step running on the device.  At most one step is queued beyond
        the running one, which holds one more cache in flight than a
        loop that reads before it dispatches.  The last token is read
        with no step after it: n-1 steps for n tokens."""
        state = result.state if isinstance(result, PrefillResult) else result
        n = self.decode_tokens if n_tokens is None else n_tokens
        logits, cache, pos = state["logits"], state["cache"], state["pos"]
        if pos + n > self.max_seq:
            raise ValueError(
                f"decode of {n} tokens from position {pos} overflows "
                f"max_seq={self.max_seq}")
        with span("decode", rows=logits.shape[0], pos=pos, steps=n,
                  dispatched=max(n - 1, 0)):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs = []
            for t in range(n):
                tok = nxt
                if t + 1 < n:
                    with span("decode.dispatch"):
                        nxt, _, cache = self._decode(
                            self.params, cache, tok, jnp.int32(pos + t))
                with span("decode.sync"):
                    outs.append(np.asarray(tok))
                if t == 0:
                    state["first_token_at"] = time.monotonic()
        return np.concatenate(outs, axis=1)

    def request_fns(self, n_tokens: int | None = None):
        """(prefill_fn, decode_fn) pair shaped for ``run_request_loop``.
        The decode_fn RETURNS its (B, n_tokens) token array — the loop
        surfaces it as ``RequestRecord.decoded`` — and also stashes it
        on the PrefillResult state as ``state["decoded"]`` for callers
        holding the prefill result."""
        def prefill_fn(toks, hits):
            return self.prefill(toks, hits)

        def decode_fn(toks, result):
            decoded = self.decode(result, n_tokens)
            result.state["decoded"] = decoded
            return decoded

        return prefill_fn, decode_fn
