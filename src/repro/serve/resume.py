"""Prefix-cache resume engine: restore cached KV slabs, prefill the
suffix from its RoPE offset, decode from the combined cache.

This is the consumer side of the Monarch prefix index — the piece that
turns an index HIT into saved prefill compute.  The flow per request
batch (driven by ``launch/serve.py::run_request_loop``):

1. ``lookup`` (through the AdmitQueue) answers which leading chunks of
   the prompt are cached — ONE fused XAM search for the whole batch.
2. :meth:`PrefixResumeEngine.prefill` fetches the hit chunks' KV slabs
   from the index's :class:`~repro.serve.kv_index.KVSlabStore`, assembles
   them into a ``prefix_kv`` pytree, and runs
   ``transformer.prefill(prefix_kv=...)`` over ONLY the suffix tokens —
   suffix positions start at the prefix length (the RoPE offset
   contract: resumed tokens attend at their original absolute
   positions), so the resulting cache and logits are bit-identical to a
   full prefill of the whole prompt.
3. The chunks it DID compute are sliced into per-chunk slabs and handed
   back (:class:`PrefillResult`), which the request loop stages via
   ``AdmitQueue.submit_tokens(toks, slabs=...)`` — submit-after-prefill,
   so the async admission worker commits slabs while decode runs.
4. :meth:`PrefixResumeEngine.decode` greedily decodes from the restored
   cache, positions continuing at the full prompt length.

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
* Only attention layers resume (``transformer.resume_supported``): SSM
  recurrent state folds the whole prefix into one vector and cannot be
  restored from per-chunk slabs.
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
# not, so axes are addressed from the right).

def _slice_chunk(tree, row: int, lo: int, hi: int):
    """One row's [lo, hi) token span of a kv pytree, as host arrays."""
    def f(a):
        sl = [slice(None)] * a.ndim
        sl[a.ndim - 4] = slice(row, row + 1)
        sl[a.ndim - 3] = slice(lo, hi)
        return np.ascontiguousarray(a[tuple(sl)])
    return jax.tree.map(f, tree)


def _concat_seq(slabs: list):
    """Concatenate per-chunk slabs along the sequence axis."""
    return jax.tree.map(
        lambda *xs: np.concatenate(xs, axis=xs[0].ndim - 3), *slabs)


def _concat_rows(rows: list):
    """Concatenate per-row prefixes along the batch axis."""
    return jax.tree.map(
        lambda *xs: np.concatenate(xs, axis=xs[0].ndim - 4), *rows)


def _nbytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


class PrefixResumeEngine:
    """Prefill/decode pair that serves prefix-cache hits from KV slabs.

    Parameters
    ----------
    params : pytree
        Model parameters (already placed on the serving mesh).
    cfg : ArchConfig
        Must be attention-only (``transformer.resume_supported``).
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
        jit the prefill/decode steps (on by default; off for debugging).
    """

    def __init__(self, params, cfg: ArchConfig, *, max_seq: int,
                 index: MonarchKVIndex, decode_tokens: int = 8,
                 jit: bool = True):
        if not transformer.resume_supported(cfg):
            raise NotImplementedError(
                f"prefix resume needs attention-only layers; {cfg.name} "
                "carries recurrent (SSM) state that chunk slabs cannot "
                "restore")
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
        self._prefill = jax.jit(fn) if jit else fn
        dec = make_decode_step(cfg)
        self._decode = jax.jit(dec) if jit else dec
        self.resumed_chunks = 0          # served from slabs, cumulative
        self.computed_chunks = 0         # recomputed, cumulative

    # ------------------------------------------------------------------
    def _resume_run(self, fps: np.ndarray, hits: np.ndarray,
                    s: int) -> int:
        """Longest leading run of chunks servable for EVERY row: the
        chunk hit in the index AND its slab resident.  Capped at
        ``(s-1) // CHUNK_TOKENS`` so at least one suffix token is always
        recomputed (last-token logits seed decode) — for chunk-aligned
        prompts that forces the last chunk out of the run; a partial
        trailing chunk is recomputed anyway and lifts the cap."""
        b, n_chunks = fps.shape
        cap = max(s - 1, 0) // CHUNK_TOKENS
        run = cap
        for r in range(b):
            k = 0
            while (k < cap and hits[r, k]
                   and self.store.get(int(fps[r, k])) is not None):
                k += 1
            run = min(run, k)
        return run

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
                run = self._resume_run(fps, np.asarray(hits, bool), s)
            p_len = run * CHUNK_TOKENS
            outer.set_metadata(prefix=p_len, suffix=s - p_len)
            if run > 0:
                with span("resume.restore", rows=b) as restore:
                    prefix_kv = _concat_rows([
                        _concat_seq([self.store.get(int(fps[r, k]))
                                     for k in range(run)])
                        for r in range(b)])
                    restore.set_metadata(nbytes=_nbytes(prefix_kv))
                    prefix_kv = jax.tree.map(jnp.asarray, prefix_kv)
                with span("resume.step"):
                    logits, cache, kv_suffix = self._prefill(
                        self.params, {"tokens": toks[:, p_len:]}, prefix_kv)
            else:
                with span("resume.step"):
                    logits, cache, kv_suffix = self._prefill(
                        self.params, {"tokens": toks})
            # Slice the freshly computed whole chunks into slabs to stage.
            with span("resume.slice") as sliced:
                kv_np = jax.tree.map(np.asarray, kv_suffix)
                slabs: dict[int, Any] = {}
                for r in range(b):
                    for c in range(run, n_chunks):
                        fp = int(fps[r, c])
                        if fp not in slabs:
                            lo = c * CHUNK_TOKENS - p_len
                            slabs[fp] = _slice_chunk(kv_np, r, lo,
                                                     lo + CHUNK_TOKENS)
                sliced.set_metadata(nbytes=sum(map(_nbytes,
                                                   slabs.values())))
        self.resumed_chunks += run * b
        self.computed_chunks += (n_chunks - run) * b
        state = {"logits": logits, "cache": cache, "pos": s}
        return PrefillResult(state=state, slabs=slabs,
                             resumed_chunks=run * b,
                             computed_chunks=(n_chunks - run) * b)

    def decode(self, result, n_tokens: int | None = None) -> np.ndarray:
        """Greedy decode from a :meth:`prefill` result (or its bare
        ``state``).  Returns the (B, n_tokens) decoded ids; positions
        continue at the full prompt length regardless of how much
        prefill was skipped.  Stamps ``state["first_token_at"]`` when the
        first token reaches the host."""
        state = result.state if isinstance(result, PrefillResult) else result
        n = self.decode_tokens if n_tokens is None else n_tokens
        logits, cache, pos = state["logits"], state["cache"], state["pos"]
        if pos + n > self.max_seq:
            raise ValueError(
                f"decode of {n} tokens from position {pos} overflows "
                f"max_seq={self.max_seq}")
        with span("decode", rows=logits.shape[0], pos=pos, steps=n):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs = []
            for t in range(n):
                # Each token is read on the host before the next step is
                # dispatched: the device idles between steps meanwhile.
                with span("decode.sync"):
                    outs.append(np.asarray(nxt))
                if t == 0:
                    state["first_token_at"] = time.monotonic()
                with span("decode.dispatch"):
                    nxt, _, cache = self._decode(
                        self.params, cache, nxt, jnp.int32(pos + t))
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
