"""Plain float32 reference for the dense decoder LMs the benchmark serves.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: no kernel,
no cache, no batching tricks, no code of the program under test.  It
follows the equations the configuration files state (``equations`` key),
taking the keys of a configuration's ``served_as`` group, where it has
one, over the published ones: it checks the model as this repo serves it.

    x   = embed[tokens] * sqrt(hidden_size)
    per layer:
        h   = rms_norm(x)                           (eps = rms_norm_eps)
        q,k,v = h Wq, h Wk, h Wv; RoPE (half-split) on q and k
        x  += softmax(q k^T / sqrt(head_dim), causal) v  Wo   (GQA)
        h   = rms_norm(x)
        x  += silu(h Wg) * (h Wu) Wd      (hidden_act "silu")
           or gelu_tanh(h Wu) Wd           (hidden_act "gelu_pytorch_tanh")
    logits = rms_norm(x) Wout

Weights are drawn from the configuration's stated init recipe
(``init`` key: normal draws under the JAX PRNG, fan-in scaled, rounded to
bfloat16, the type they are served in), one layer at a time inside one
jitted program per layer, so the whole float32 model is never resident.

``quant`` gives the control: the same forward with every weight matrix
rounded to int8 (per output channel) or float8 e4m3, the precision step
below the bfloat16 the configurations serve in.

Every configuration with ``"reference": "dense_lm"`` is checked by
``logits`` and counted by ``prefill_flops``, ``decode_flops`` and
``decode_bytes`` here (``cost`` dispatches to them): attention and the
MLP of every layer for each computed token, the output head for the
one position a prefill or a decode step answers.  These four functions
are what every reference module provides (``reference/__init__.py``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

BF16 = 2


def _bf16_normal(key, shape, scale):
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _quantize(w, quant, axis):
    """Round ``w`` to ``quant`` with one scale per slice along ``axis``."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


def model_keys(cfg: dict):
    """(embedding key, per-layer keys) of the init recipe."""
    k = jax.random.split(jax.random.PRNGKey(cfg["init"]["seed"]), 4)
    return k[0], jax.random.split(k[1], cfg["num_hidden_layers"])


def layer_weights(key, cfg: dict, quant=None):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    block = jax.random.split(key, 1)[0]
    k_attn, k_mlp, _ = jax.random.split(block, 3)
    a = jax.random.split(k_attn, 4)
    m = jax.random.split(k_mlp, 3)
    w = {"wq": _bf16_normal(a[0], (d, h * dh), d ** -0.5),
         "wk": _bf16_normal(a[1], (d, kv * dh), d ** -0.5),
         "wv": _bf16_normal(a[2], (d, kv * dh), d ** -0.5),
         "wo": _bf16_normal(a[3], (h * dh, d), (h * dh) ** -0.5),
         "w_up": _bf16_normal(m[0], (d, f), d ** -0.5),
         "w_down": _bf16_normal(m[1], (f, d), f ** -0.5)}
    if cfg["hidden_act"] == "silu":
        w["w_gate"] = _bf16_normal(m[2], (d, f), d ** -0.5)
    return {n: _quantize(x, quant, axis=0) for n, x in w.items()}


def embed_weights(key, cfg: dict, quant=None):
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    k = jax.random.split(key, 2)
    return (_quantize(_bf16_normal(k[0], (v, d), 0.02), quant, axis=1),
            _quantize(_bf16_normal(k[1], (d, v), d ** -0.5), quant, axis=0))


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """x: (S, H, dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_row(q, k, v, scale):
    """One sequence: q (S, H, dh), k/v (S, KV, dh); causal softmax."""
    s, h, _ = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def _layer(w, x, cfg):
    """x: (B, S, D) float32 -> (B, S, D)."""
    b, s, _ = x.shape
    h_, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    h = _rms_norm(x, eps)
    q = (h @ w["wq"]).reshape(b, s, h_, dh)
    k = (h @ w["wk"]).reshape(b, s, kv, dh)
    v = (h @ w["wv"]).reshape(b, s, kv, dh)

    def row(qkv):
        qr, kr, vr = qkv
        return _attention_row(_rope(qr, pos, theta), _rope(kr, pos, theta),
                              vr, dh ** -0.5)

    att = jax.lax.map(row, (q, k, v)).reshape(b, s, h_ * dh)
    x = x + att @ w["wo"]
    h = _rms_norm(x, eps)
    if cfg["hidden_act"] == "silu":
        up = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    elif cfg["hidden_act"] == "gelu_pytorch_tanh":
        up = jax.nn.gelu(h @ w["w_up"], approximate=True)
    else:
        raise ValueError(f"unknown hidden_act {cfg['hidden_act']!r}")
    return x + up @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_step(key, x, cfg_items, quant):
    cfg = dict(cfg_items)
    return _layer(layer_weights(key, cfg, quant), x, cfg)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _embed_step(key, tokens, cfg_items, quant):
    cfg = dict(cfg_items)
    emb, _ = embed_weights(key, cfg, quant)
    return emb[tokens] * jnp.float32(cfg["hidden_size"] ** 0.5)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "first"))
def _head_step(key, x, cfg_items, quant, first):
    cfg = dict(cfg_items)
    _, unembed = embed_weights(key, cfg, quant)
    return _rms_norm(x[:, first:], cfg["rms_norm_eps"]) @ unembed


def _static(cfg: dict):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps",
            "rope_theta", "hidden_act")
    return tuple((k, cfg[k]) for k in keys)


def logits(cfg: dict, tokens: np.ndarray, first: int, quant=None):
    """Reference logits of ``tokens`` (B, L) at positions ``first..L-1``.

    Returns a (B, L - first, vocab) float32 host array: row t holds the
    logits that predict token ``first + t + 1``."""
    cfg = {**cfg, **cfg.get("served_as", {})}
    items = _static(cfg)
    k_embed, k_layers = model_keys(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed_step(k_embed, jnp.asarray(tokens, jnp.int32), items, quant)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer_step(k_layers[i], x, items, quant)
        out = _head_step(k_embed, x, items, quant, first)
    return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# Counts: what a prefill or a decode step needs, from shapes alone.
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: dict) -> int:
    """Weights one decoder layer multiplies by (attention + MLP)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * f
    return d * q + 2 * d * kv + q * d + mlp


def weight_bytes(cfg: dict) -> int:
    """Bytes of every served weight a decode step must read: all layers
    and the output head (the embedding table is gathered a row a token)."""
    return BF16 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg: dict) -> int:
    return (BF16 * 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])


def _attn_flops(cfg: dict, q_tokens: int, first_pos: int) -> float:
    """Causal attention of ``q_tokens`` queries at positions
    ``first_pos..first_pos+q_tokens-1``: QK^T and PV, keys up to and
    including each query's own position."""
    keys = q_tokens * first_pos + q_tokens * (q_tokens + 1) / 2
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * keys)


def prefill_flops(cfg: dict, rows: int, prefix: int, suffix: int) -> float:
    """Model FLOPs of a prefill that computes ``suffix`` tokens per row
    after a restored ``prefix``: the layers for every computed token,
    attention over prefix and suffix, the output head for the last."""
    lin = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * suffix
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return rows * (lin + _attn_flops(cfg, suffix, prefix) + head)


def decode_flops(cfg: dict, rows: int, pos: int) -> float:
    """One decode step writing position ``pos`` for ``rows`` rows."""
    lin = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return rows * (lin + _attn_flops(cfg, 1, pos) + head)


def decode_bytes(cfg: dict, rows: int, pos: int) -> float:
    """Bytes one decode step must move: the weights once, each row's
    embedding row and cache keys/values up to ``pos``, and the new
    key/value it writes."""
    kv = kv_bytes_per_token(cfg)
    return (weight_bytes(cfg)
            + rows * (BF16 * cfg["hidden_size"] + kv * (pos + 1) + kv))
