"""Core transformer layers: norms, RoPE, GQA attention (chunked/flash),
gated MLP, embeddings.  Pure functions over parameter dicts.

Conventions:
* params are nested dicts of jnp arrays; a parallel tree of
  ``jax.sharding.PartitionSpec`` is built by ``repro.dist.sharding``.
* compute dtype bf16, accumulations fp32 (``preferred_element_type``).
* attention is chunked over KV (online softmax) so the 32k/500k shapes
  never materialize (Q, K) score planes; the chunk body is rematted.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

DTYPE = jnp.bfloat16
NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# Init helpers.
# ---------------------------------------------------------------------------

def dense_init(key, shape, scale: float | None = None, dtype=DTYPE):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE.
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig):
    k = split_keys(key, 4)
    return {
        "wq": dense_init(k[0], (cfg.d_model, cfg.n_heads * cfg.d_head)),
        "wk": dense_init(k[1], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
        "wv": dense_init(k[2], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
        "wo": dense_init(k[3], (cfg.n_heads * cfg.d_head, cfg.d_model)),
    }


def _qkv(params, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = (x @ params["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = (x @ params["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _soft_cap(logits, cap: float):
    if cap and cap > 0:
        return jnp.tanh(logits / cap) * cap
    return logits


def attn_scale(cfg: ArchConfig) -> float:
    """Softmax scale: the published ``attention_multiplier``, else
    d_head ** -0.5."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return cfg.d_head ** -0.5


def chunked_attention(q, k, v, *, causal: bool, window: int, softcap: float,
                      q_offset, kv_chunk: int = 1024, scale=None):
    """Online-softmax attention, scanning KV in chunks.

    q: (B, Sq, H, dh); k/v: (B, Skv, KV, dh).  ``q_offset`` = absolute
    position of q[0] relative to k[0] (0 for self-attn; >0 for decode).
    window > 0 applies sliding-window masking (local attention).
    ``scale`` defaults to dh ** -0.5.  Returns (B, Sq, H, dh).
    """
    b, sq, h, dh = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = dh ** -0.5 if scale is None else scale
    kv_chunk = min(kv_chunk, skv)
    n_chunks = (skv + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # GQA grouping WITHOUT repeating KV: q (B, Sq, KVH, rep, dh).
    qg = (q * scale).astype(DTYPE).reshape(b, sq, kvh, rep, dh)
    q_pos = q_offset + jnp.arange(sq)                        # (Sq,)

    def body(carry, chunk_idx):
        m, l, acc = carry
        start = chunk_idx * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
        k_pos = start + jnp.arange(kv_chunk)                 # (C,)
        # (B, KVH, rep, Sq, C) logits — KV heads broadcast, never repeated.
        logits = jnp.einsum("bqgrd,bcgd->bgrqc", qg, kc,
                            preferred_element_type=jnp.float32)
        logits = _soft_cap(logits, softcap)
        mask = (k_pos[None, :] < skv)                        # padding
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window and window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None, :, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqc,bcgd->bgrqd", p.astype(DTYPE), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    acc0 = jnp.zeros((b, kvh, rep, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-20)[..., None]              # (B,KVH,rep,Sq,dh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh)
    return out.astype(q.dtype)


def _seq_shard(t, cfg: ArchConfig):
    """§Perf: pin (B, S, ...) activations to (dp, model, None...) so the
    attention einsums contract UNsharded head dims (no fp32-logits
    all-reduce) at the cost of gathering KV chunks over `model`."""
    axes = tuple(getattr(cfg, "attn_seq_shard", ()) or ())
    if not axes:
        return t
    from jax.sharding import PartitionSpec as P
    spec = P(axes, "model", *([None] * (t.ndim - 2)))
    return jax.lax.with_sharding_constraint(t, spec)


def attention_block(params, x, cfg: ArchConfig, positions, *, local: bool):
    """Self-attention over x (B, S, D)."""
    q, k, v = _qkv(params, x, cfg, positions)
    q, k, v = _seq_shard(q, cfg), _seq_shard(k, cfg), _seq_shard(v, cfg)
    window = cfg.sliding_window if local else 0
    out = chunked_attention(
        q, k, v, causal=cfg.causal and not cfg.encoder_only,
        window=window, softcap=cfg.logit_softcap, q_offset=0,
        kv_chunk=cfg.attn_kv_chunk, scale=attn_scale(cfg))
    b, s, _, _ = out.shape
    return _seq_shard(out.reshape(b, s, -1) @ params["wo"], cfg)


def decode_attention(params, x, cfg: ArchConfig, cache_k, cache_v, pos,
                     *, local: bool):
    """Single-token decode: x (B, 1, D); cache_k/v (B, S_max, KV, dh);
    ``pos`` scalar int32 — index of the new token.  Returns
    (out (B,1,D), new_k, new_v)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = (x @ params["wq"]).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k_new = (x @ params["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v_new = (x @ params["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), pos, axis=1)

    s_max = cache_k.shape[1]
    kvh = cfg.n_kv_heads
    rep = cfg.n_heads // kvh
    scale = attn_scale(cfg)
    qg = (q * scale).astype(DTYPE).reshape(b, 1, kvh, rep, cfg.d_head)
    logits = jnp.einsum("bqgrd,bcgd->bgrqc", qg, cache_k.astype(DTYPE),
                        preferred_element_type=jnp.float32)
    logits = _soft_cap(logits, cfg.logit_softcap)
    k_pos = jnp.arange(s_max)
    mask = k_pos <= pos
    if local:
        mask = mask & (k_pos > pos - cfg.sliding_window)
    logits = jnp.where(mask[None, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(DTYPE)
    out = jnp.einsum("bgrqc,bcgd->bqgrd", p, cache_v.astype(DTYPE),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out.reshape(b, 1, -1) @ params["wo"]
    return out, cache_k, cache_v


def decode_attention_ring(params, x, cfg: ArchConfig, cache_k, cache_v, pos,
                          slot):
    """Sliding-window decode with a ring-buffer cache of size W: slot =
    pos % W.  Keys are stored post-RoPE (absolute positions), so slot s
    holds absolute position  p_s = pos - ((pos - s) mod W)  — always inside
    the window; only p_s >= 0 entries are valid.  Cache memory is O(W)
    instead of O(S_max): the 500k-context local layers cost 1024 slots."""
    b = x.shape[0]
    w = cache_k.shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = (x @ params["wq"]).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k_new = (x @ params["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v_new = (x @ params["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), slot, axis=1)

    kvh = cfg.n_kv_heads
    rep = cfg.n_heads // kvh
    scale = attn_scale(cfg)
    qg = (q * scale).astype(DTYPE).reshape(b, 1, kvh, rep, cfg.d_head)
    logits = jnp.einsum("bqgrd,bcgd->bgrqc", qg, cache_k.astype(DTYPE),
                        preferred_element_type=jnp.float32)
    logits = _soft_cap(logits, cfg.logit_softcap)
    s_ix = jnp.arange(w)
    abs_pos = pos - ((pos - s_ix) % w)
    mask = abs_pos >= 0
    logits = jnp.where(mask[None, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(DTYPE)
    out = jnp.einsum("bgrqc,bcgd->bqgrd", p, cache_v.astype(DTYPE),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out.reshape(b, 1, -1) @ params["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    k = split_keys(key, 3)
    p = {
        "w_up": dense_init(k[0], (cfg.d_model, d_ff)),
        "w_down": dense_init(k[1], (d_ff, cfg.d_model)),
    }
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(k[2], (cfg.d_model, d_ff))
    return p


def mlp_block(params, x, cfg: ArchConfig):
    up = x @ params["w_up"]
    if cfg.mlp_gated:
        up = jax.nn.silu(x @ params["w_gate"]) * up
    else:
        up = jax.nn.gelu(up)
    return up @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding with seq-chunked loss.
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ArchConfig):
    k = split_keys(key, 2)
    p = {"embed": dense_init(k[0], (cfg.vocab_size, cfg.d_model),
                             scale=cfg.embed_init_std)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k[1], (cfg.d_model, cfg.vocab_size))
    return p


def embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def unembed_logits(params, x):
    w = params.get("unembed")
    if w is None:
        w = params["embed"].T
    return jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)


def chunked_ce_loss(params, x, labels, *, chunk: int = 512,
                    logits_scaling: float = 1.0):
    """Cross-entropy over the vocab, scanning sequence chunks so the full
    (B, S, V) logits plane is never resident (rematted chunk body).
    The logits are divided by ``logits_scaling`` (granite's)."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    pad = n_chunks * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)

    def body(total, idx):
        xc = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        logits = unembed_logits(params, xc)                   # (B, C, V) fp32
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0)
        nll = jnp.where(valid, logz - gold, 0.0)
        return (total[0] + nll.sum(), total[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        jnp.arange(n_chunks))
    return tot / jnp.maximum(cnt, 1).astype(jnp.float32)
