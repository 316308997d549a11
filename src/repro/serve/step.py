"""Serving steps: prefill and single-token decode, jit/shard-ready."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer


def make_prefill_step(cfg: ArchConfig, max_seq: int):
    def prefill_step(params, batch):
        return transformer.prefill(params, cfg, batch, max_seq)
    return prefill_step


def make_resume_prefill_step(cfg: ArchConfig, max_seq: int):
    """Prefill-from-offset for the prefix-cache resume path.

    ``prefix_kv`` holds the cached prefix's post-RoPE per-layer k/v
    (``None`` = ordinary full prefill); ``batch`` holds only the suffix
    tokens, which attend at absolute positions starting at the prefix
    length (the RoPE offset contract).  Always returns
    ``(last-token logits, decode cache, kv-of-this-call)`` — the kv
    pytree is what the caller slices into per-chunk slabs to stage for
    admission.  jit-compatible: prefix/suffix lengths are static shapes,
    so each distinct (P, S_suffix) pair compiles once.

    A recurrent (Mamba-2 hybrid) arch also takes ``prefix_state``, the
    state snapshot at the prefix's end, and ``snapshot_at`` (static: jit
    it with ``static_argnames="snapshot_at"``), which appends the state
    snapshot at that token to the returns.
    """
    def resume_prefill_step(params, batch, prefix_kv=None,
                            prefix_state=None, snapshot_at=None):
        return transformer.prefill(params, cfg, batch, max_seq,
                                   prefix_kv=prefix_kv,
                                   prefix_state=prefix_state,
                                   return_kv=True, snapshot_at=snapshot_at)
    return resume_prefill_step


def make_decode_step(cfg: ArchConfig, greedy: bool = True):
    def serve_step(params, cache, tokens, pos):
        """tokens: (B, 1) int32; pos: scalar int32.
        Returns (next_tokens (B, 1), logits (B, V), new cache)."""
        logits, cache = transformer.decode_step(params, cfg, tokens, cache, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache
    return serve_step
