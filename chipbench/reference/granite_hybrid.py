"""Plain float32 reference for granite-4.0-h, a hybrid of Mamba-2 and
attention layers (``model_type`` ``granitemoehybrid`` without experts).

Straightforward ``jax.numpy`` at ``highest`` matmul precision: no kernel,
no cache, no chunked scan, no code of the program under test.  It
follows the equations the configuration file states (``equations``):

    x   = embed[tokens] * embedding_multiplier
    per layer (layer_types[i] is "mamba" or "attention"):
        h   = rms_norm(x)                               (eps = rms_norm_eps)
        x  += residual_multiplier * mixer(h)
        h   = rms_norm(x)
        x  += residual_multiplier * silu(h Wg) * (h Wu) Wd
    logits = rms_norm(x) embed^T / logits_scaling       (tied embeddings)

    attention: q,k,v = h Wq, h Wk, h Wv with no positional encoding;
        softmax(q k^T * attention_multiplier, causal) v Wo   (GQA)
    mamba (Mamba-2, one group): z = h Wz, xBC = h WxBC, dt = h Wdt;
        xBC = silu(causal depthwise conv(xBC) + conv bias); x, B, C = xBC;
        dt = softplus(dt + dt_bias), A = -exp(A_log), per head and token
            state <- exp(dt A) state + dt x B^T,   y = state C + D x
        (the sequential recurrence, token by token, not the chunked SSD);
        out = rms_norm(y * silu(z)) Wout      (gated norm over d_inner)

Weights are drawn from the configuration's stated init recipe (``init``
key: the program's layer-group key split, normal draws fan-in scaled
and the embedding's at ``embedding_std``, rounded to bfloat16, the type
they are served in; dt_bias spread geometrically over ``dt_range``),
one layer at a time inside one jitted program per layer, so the whole
float32 model is never resident.  ``quant`` gives the control: every weight matrix
rounded to int8 (per output channel) or float8 e4m3.

``prefill_flops``, ``decode_flops`` and ``decode_bytes`` count a model
step from shapes: the projections, conv, MLP and head of every computed
token, attention over the keys it sees, and the SSD as its recurrence
needs it (per token and head: the state's decay, its update by dt x B^T
and the read-out by C, 5 P N operations; the chunked program does more).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

BF16, F32 = 2, 4


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


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    n = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"d": d, "di": di, "n": cfg["mamba_d_state"],
            "h": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
            "k": cfg["mamba_d_conv"], "conv": di + 2 * n,
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "dh": d // cfg["num_attention_heads"],
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"]}


# ---------------------------------------------------------------------------
# Weights: the init recipe.
# ---------------------------------------------------------------------------

def model_keys(cfg: dict):
    """(embedding key, per-layer keys): layers are drawn in groups of
    ``init.group_layers`` (one key a group, split over its layers), the
    layers past the last whole group from keys of their own."""
    n_layers, g = cfg["num_hidden_layers"], cfg["init"]["group_layers"]
    n_groups = n_layers // g
    top = jax.random.split(jax.random.PRNGKey(cfg["init"]["seed"]),
                           4 + n_layers - n_groups * g)
    keys = []
    for gk in jax.random.split(top[1], n_groups):
        keys.extend(jax.random.split(gk, g))
    keys.extend(top[4:])
    return top[0], keys


def layer_weights(key, cfg: dict, kind: str, quant=None):
    m = _dims(cfg)
    d, f = m["d"], m["f"]
    k_mix, k_mlp, _ = jax.random.split(key, 3)
    mk = jax.random.split(k_mlp, 3)
    w = {"w_up": _bf16_normal(mk[0], (d, f), d ** -0.5),
         "w_down": _bf16_normal(mk[1], (f, d), f ** -0.5),
         "w_gate": _bf16_normal(mk[2], (d, f), d ** -0.5)}
    if kind == "attention":
        a = jax.random.split(k_mix, 4)
        q, kv = m["heads"] * m["dh"], m["kv"] * m["dh"]
        w |= {"wq": _bf16_normal(a[0], (d, q), d ** -0.5),
              "wk": _bf16_normal(a[1], (d, kv), d ** -0.5),
              "wv": _bf16_normal(a[2], (d, kv), d ** -0.5),
              "wo": _bf16_normal(a[3], (q, d), q ** -0.5)}
    elif kind == "mamba":
        s = jax.random.split(k_mix, 4)
        di, c = m["di"], m["conv"]
        w |= {"wz": _bf16_normal(s[0], (d, di), d ** -0.5),
              "wxbc": _bf16_normal(s[3], (d, c), d ** -0.5),
              "wdt": _bf16_normal(s[1], (d, m["h"]), 0.02),
              "conv_w": _bf16_normal(s[1], (m["k"], c), 0.5),
              "wout": _bf16_normal(s[2], (di, d), di ** -0.5)}
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return {name: _quantize(x, quant, axis=0) for name, x in w.items()}


def mamba_vectors(cfg: dict) -> dict:
    """The Mamba-2 layers' per-head and per-channel parameters, the same
    in every layer: A_log, dt_bias, D and the conv bias."""
    m = _dims(cfg)
    lo, hi = cfg["init"]["dt_range"]
    return {"a_log": jnp.log(jnp.linspace(1.0, 16.0, m["h"])),
            "dt_bias": jnp.log(jnp.expm1(jnp.geomspace(lo, hi, m["h"]))),
            "d_skip": jnp.ones((m["h"],), jnp.float32),
            "conv_b": jnp.zeros((m["conv"],), jnp.float32)}


def embed_weights(key, cfg: dict, quant=None):
    m = _dims(cfg)
    k = jax.random.split(key, 2)
    return _quantize(_bf16_normal(k[0], (m["v"], m["d"]),
                                  cfg["init"]["embedding_std"]),
                     quant, axis=1)


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------

def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _attention(w, h, cfg, m):
    """Causal NoPE GQA over (B, S, D), one row and one KV head at a time."""
    b, s, _ = h.shape
    rep = m["heads"] // m["kv"]
    q = (h @ w["wq"]).reshape(b, s, m["kv"], rep, m["dh"])
    k = (h @ w["wk"]).reshape(b, s, m["kv"], m["dh"])
    v = (h @ w["wv"]).reshape(b, s, m["kv"], m["dh"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):
        qh, kh, vh = qkv                           # (S, rep, dh), (S, dh)
        scores = jnp.einsum("qrd,kd->rqk", qh, kh) * cfg["attention_multiplier"]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(scores, axis=-1), vh)

    def row(qkv):
        qr, kr, vr = qkv
        out = jax.lax.map(head, (jnp.moveaxis(qr, 1, 0), jnp.moveaxis(kr, 1, 0),
                                 jnp.moveaxis(vr, 1, 0)))
        return jnp.moveaxis(out, 0, 1)             # (S, KV, rep, dh)

    att = jax.lax.map(row, (q, k, v)).reshape(b, s, m["heads"] * m["dh"])
    return att @ w["wo"]


def _mamba(w, h, cfg, m):
    """Mamba-2 over (B, S, D) by its sequential recurrence."""
    b, s, _ = h.shape
    vec = mamba_vectors(cfg)
    z = h @ w["wz"]
    xbc = h @ w["wxbc"]
    dt = jax.nn.softplus(h @ w["wdt"] + vec["dt_bias"])          # (B, S, H)
    kk = m["k"]
    xp = jnp.pad(xbc, ((0, 0), (kk - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * w["conv_w"][i] for i in range(kk))
    xbc = jax.nn.silu(conv + vec["conv_b"])
    x, bm, cm = jnp.split(xbc, [m["di"], m["di"] + m["n"]], axis=-1)
    x = x.reshape(b, s, m["h"], m["p"])
    a = -jnp.exp(vec["a_log"])                                   # (H,)

    def step(state, t):
        xt, bt, ct, dtt = t                        # (B,H,P) (B,N) (B,N) (B,H)
        state = (jnp.exp(dtt * a)[:, :, None, None] * state
                 + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", state, ct) + vec["d_skip"][:, None] * xt
        return state, y

    state0 = jnp.zeros((b, m["h"], m["p"], m["n"]), jnp.float32)
    _, y = jax.lax.scan(step, state0, (jnp.moveaxis(x, 1, 0),
                                       jnp.moveaxis(bm, 1, 0),
                                       jnp.moveaxis(cm, 1, 0),
                                       jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, m["di"])
    y = _rms_norm(y * jax.nn.silu(z), cfg["rms_norm_eps"])
    return y @ w["wout"]


def _layer(w, x, cfg, kind):
    m = _dims(cfg)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = _rms_norm(x, eps)
    mix = _attention if kind == "attention" else _mamba
    x = x + r * mix(w, h, cfg, m)
    h = _rms_norm(x, eps)
    up = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    return x + r * (up @ w["w_down"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "quant"))
def _layer_step(key, x, cfg_items, kind, quant):
    cfg = _unfreeze(cfg_items)
    return _layer(layer_weights(key, cfg, kind, quant), x, cfg, kind)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _embed_step(key, tokens, cfg_items, quant):
    cfg = _unfreeze(cfg_items)
    return embed_weights(key, cfg, quant)[tokens] * jnp.float32(
        cfg["embedding_multiplier"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant", "first"))
def _head_step(key, x, cfg_items, quant, first):
    cfg = _unfreeze(cfg_items)
    emb = embed_weights(key, cfg, quant)
    return (_rms_norm(x[:, first:], cfg["rms_norm_eps"]) @ emb.T
            / jnp.float32(cfg["logits_scaling"]))


def _unfreeze(cfg_items) -> dict:
    cfg = dict(cfg_items)
    cfg["init"] = dict(cfg["init"])
    return cfg


def _static(cfg: dict):
    """The configuration's keys the forward reads, hashable (a jit
    static argument)."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "rms_norm_eps",
            "mamba_expand", "mamba_n_groups", "mamba_d_state",
            "mamba_n_heads", "mamba_d_head", "mamba_d_conv",
            "embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling")
    init = cfg["init"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("init", (("embedding_std", init["embedding_std"]),
                  ("dt_range", tuple(init["dt_range"])))),)


def logits(cfg: dict, tokens: np.ndarray, first: int, quant=None):
    """Reference logits of ``tokens`` (B, L) at positions ``first..L-1``.

    Returns a (B, L - first, vocab) float32 host array: row t holds the
    logits that predict token ``first + t + 1``."""
    items = _static(cfg)
    k_embed, k_layers = model_keys(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed_step(k_embed, jnp.asarray(tokens, jnp.int32), items, quant)
        for key, kind in zip(k_layers, cfg["layer_types"]):
            x = _layer_step(key, x, items, kind, quant)
        out = _head_step(k_embed, x, items, quant, first)
    return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# Counts: what a prefill or a decode step needs, from shapes alone.
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg: dict, kind: str) -> int:
    """Weights one layer multiplies by: its mixer's projections (and a
    Mamba layer's conv taps) and its MLP."""
    m = _dims(cfg)
    d = m["d"]
    mlp = 3 * d * m["f"]
    if kind == "attention":
        q, kv = m["heads"] * m["dh"], m["kv"] * m["dh"]
        return d * q + 2 * d * kv + q * d + mlp
    return (d * m["di"] + d * m["conv"] + d * m["h"] + m["di"] * d
            + m["k"] * m["conv"] + mlp)


def _kinds(cfg: dict) -> dict:
    types = cfg["layer_types"]
    return {"attention": types.count("attention"),
            "mamba": types.count("mamba")}


def _linear_flops(cfg: dict) -> float:
    """Per computed token: the matmuls and conv of every layer and the
    SSD recurrence of every Mamba layer."""
    m, n = _dims(cfg), _kinds(cfg)
    ssd = 5.0 * m["h"] * m["p"] * m["n"]
    return sum(cnt * 2.0 * layer_matmul_params(cfg, kind)
               for kind, cnt in n.items()) + n["mamba"] * ssd


def _head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def _attn_flops(cfg: dict, q_tokens: int, first_pos: int) -> float:
    """Causal attention of ``q_tokens`` queries at positions
    ``first_pos..first_pos+q_tokens-1`` in every attention layer: QK^T
    and PV over the keys up to and including each query's position."""
    m = _dims(cfg)
    keys = q_tokens * first_pos + q_tokens * (q_tokens + 1) / 2
    return 4.0 * _kinds(cfg)["attention"] * m["heads"] * m["dh"] * keys


def prefill_flops(cfg: dict, rows: int, prefix: int, suffix: int) -> float:
    """Model FLOPs of a prefill that computes ``suffix`` tokens per row
    after a restored ``prefix`` (its KV and the Mamba state at its end):
    the layers for every computed token, attention over prefix and
    suffix, the output head for the last."""
    return rows * (_linear_flops(cfg) * suffix
                   + _attn_flops(cfg, suffix, prefix) + _head_flops(cfg))


def decode_flops(cfg: dict, rows: int, pos: int) -> float:
    """One decode step writing position ``pos`` for ``rows`` rows."""
    return rows * (_linear_flops(cfg) + _attn_flops(cfg, 1, pos)
                   + _head_flops(cfg))


def weight_bytes(cfg: dict) -> int:
    """Bytes of the served weight matrices a decode step reads: every
    layer's and the tied embedding as the output head.  The per-channel
    vectors (norms, biases, A, D), under 0.1% of them, are left out."""
    return BF16 * (sum(cnt * layer_matmul_params(cfg, kind)
                       for kind, cnt in _kinds(cfg).items())
                   + cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg: dict) -> int:
    m = _dims(cfg)
    return BF16 * 2 * _kinds(cfg)["attention"] * m["kv"] * m["dh"]


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state: each Mamba layer's float32 SSM state
    and its conv tail of d_conv - 1 bfloat16 inputs."""
    m = _dims(cfg)
    return _kinds(cfg)["mamba"] * (F32 * m["h"] * m["p"] * m["n"]
                                   + BF16 * (m["k"] - 1) * m["conv"])


def decode_bytes(cfg: dict, rows: int, pos: int) -> float:
    """Bytes one decode step must move: the weights once; for each row
    its embedding row, its recurrent state read and written, and the
    attention layers' cache keys/values up to ``pos`` and the new
    key/value it writes."""
    kv = kv_bytes_per_token(cfg)
    return (weight_bytes(cfg)
            + rows * (BF16 * cfg["hidden_size"] + 2 * state_bytes(cfg)
                      + kv * (pos + 1) + kv))
