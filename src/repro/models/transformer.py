"""Model assembly: composable blocks -> scan-over-layer-groups stack.

The per-layer pattern of each architecture (dense / 5:1 local:global /
MoE / Mamba / hybrid-with-shared-attention) is factored into a repeating
*group* that is scanned with stacked parameters (plus an unscanned
remainder), so HLO size and compile time are independent of depth — a 62
layer model lowers as one group body.

Public entry points (used by train/serve/launch):

    init_params(key, cfg)                      -> params pytree
    forward(params, cfg, batch)                -> final hidden states
    train_loss(params, cfg, batch)             -> scalar CE loss
    init_cache(cfg, batch, max_seq)            -> decode cache pytree
    prefill(params, cfg, batch, cache)         -> (last-token logits, cache)
    decode_step(params, cfg, tokens, cache, pos) -> (logits, cache)

Batch dict keys: "tokens" (B, S) int32 and/or "embeds" (B, P, D) bf16
(VLM patch / audio frame stubs), "labels" (B, S) int32 (-1 = masked).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, MAMBA1, MAMBA2,
                                SHARED_ATTN, ArchConfig)
from repro.models import layers, moe, ssm

DTYPE = layers.DTYPE


# ---------------------------------------------------------------------------
# Per-block init / apply.
# ---------------------------------------------------------------------------

def _is_attn(kind: str) -> bool:
    return kind in (ATTN_GLOBAL, ATTN_LOCAL, SHARED_ATTN)


def init_block(key, kind: str, cfg: ArchConfig):
    k = layers.split_keys(key, 3)
    p = {"ln1": jnp.zeros((cfg.d_model,), DTYPE)}
    if _is_attn(kind):
        p["attn"] = layers.init_attention(k[0], cfg)
        p["ln2"] = jnp.zeros((cfg.d_model,), DTYPE)
        if cfg.n_experts and kind != SHARED_ATTN:
            p["moe"] = moe.init_moe(k[1], cfg)
        else:
            p["mlp"] = layers.init_mlp(k[1], cfg)
    elif kind == MAMBA1:
        p["ssm"] = ssm.init_mamba1(k[0], cfg)
    elif kind == MAMBA2:
        p["ssm"] = ssm.init_mamba2(k[0], cfg)
    else:
        raise ValueError(kind)
    return p


def apply_block(p, kind: str, x, cfg: ArchConfig, positions):
    h = layers.rms_norm(x, p["ln1"])
    if _is_attn(kind):
        h = layers.attention_block(p["attn"], h, cfg, positions,
                                   local=(kind == ATTN_LOCAL))
        x = x + h
        h2 = layers.rms_norm(x, p["ln2"])
        if "moe" in p:
            h2 = moe.moe_block(p["moe"], h2, cfg)
        else:
            h2 = layers.mlp_block(p["mlp"], h2, cfg)
        return x + h2
    else:
        fn = ssm.mamba1_block if kind == MAMBA1 else ssm.mamba2_block
        return x + fn(p["ssm"], h, cfg)


# ---------------------------------------------------------------------------
# Parameter tree.
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig):
    group, n_groups, rem = cfg.scan_groups()
    keys = layers.split_keys(key, 4 + len(rem))
    params = {"embed": layers.init_embed(keys[0], cfg),
              "final_ln": jnp.zeros((cfg.d_model,), DTYPE)}

    if n_groups > 0:
        def init_one_group(gkey):
            ks = layers.split_keys(gkey, len(group))
            return {f"b{i}": init_block(ks[i], kind, cfg)
                    for i, kind in enumerate(group)
                    if kind != SHARED_ATTN}
        gkeys = jnp.stack(layers.split_keys(keys[1], n_groups))
        # lax.map, not vmap: one group's draw at a time, so a jitted init
        # holds one layer's float32 transient, never a whole stack's.
        params["groups"] = jax.lax.map(init_one_group, gkeys)
    if any(k == SHARED_ATTN for k in group + rem):
        params["shared"] = init_block(keys[2], SHARED_ATTN, cfg)
    for i, kind in enumerate(rem):
        if kind != SHARED_ATTN:
            params[f"rem{i}"] = init_block(keys[4 + i], kind, cfg)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward (training / encoder / prefill trunk).
# ---------------------------------------------------------------------------

def _input_embeds(params, cfg: ArchConfig, batch):
    parts = []
    if "embeds" in batch:
        parts.append(batch["embeds"].astype(DTYPE))
    if "tokens" in batch:
        scale = jnp.asarray(cfg.d_model ** 0.5, DTYPE)
        parts.append(layers.embed(params["embed"], batch["tokens"]) * scale)
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, positions


def forward(params, cfg: ArchConfig, batch):
    x, positions = _input_embeds(params, cfg, batch)
    group, n_groups, rem = cfg.scan_groups()
    shared = params.get("shared")

    if n_groups > 0:
        def body(xc, gp):
            for i, kind in enumerate(group):
                p = shared if kind == SHARED_ATTN else gp[f"b{i}"]
                xc = apply_block(p, kind, xc, cfg, positions)
            return xc, None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["groups"])
    for i, kind in enumerate(rem):
        p = shared if kind == SHARED_ATTN else params[f"rem{i}"]
        x = apply_block(p, kind, x, cfg, positions)
    return layers.rms_norm(x, params["final_ln"])


def train_loss(params, cfg: ArchConfig, batch):
    x = forward(params, cfg, batch)
    labels = batch["labels"]
    if "embeds" in batch and "tokens" in batch:
        # VLM: loss only over the text tail (prefix embeds carry no labels).
        x = x[:, batch["embeds"].shape[1]:]
    loss = layers.chunked_ce_loss(params["embed"], x, labels)
    if cfg.n_experts:
        # aux load-balance term over the last hidden states (cheap proxy;
        # the per-layer routers see rebalanced inputs anyway).
        pass
    return loss


# ---------------------------------------------------------------------------
# Decode caches.
# ---------------------------------------------------------------------------

def _block_cache(kind: str, cfg: ArchConfig, b: int, max_seq: int):
    if _is_attn(kind):
        s = min(max_seq, cfg.sliding_window) if kind == ATTN_LOCAL else max_seq
        shape = (b, s, cfg.n_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shape, DTYPE), "v": jnp.zeros(shape, DTYPE)}
    if kind == MAMBA1:
        di = ssm.d_inner(cfg)
        return {"h": jnp.zeros((b, di, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((b, cfg.ssm_conv - 1, di), DTYPE)}
    if kind == MAMBA2:
        di = ssm.d_inner(cfg)
        return {"h": jnp.zeros((b, ssm.m2_heads(cfg), cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((b, cfg.ssm_conv - 1, di + 2 * cfg.ssm_state),
                                  DTYPE)}
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int):
    group, n_groups, rem = cfg.scan_groups()
    cache = {}
    if n_groups > 0:
        def one(_):
            return {f"b{i}": _block_cache(kind, cfg, batch, max_seq)
                    for i, kind in enumerate(group)}
        cache["groups"] = jax.vmap(one)(jnp.arange(n_groups))
    for i, kind in enumerate(rem):
        cache[f"rem{i}"] = _block_cache(kind, cfg, batch, max_seq)
    return cache


def _decode_block(p, kind: str, x, cfg: ArchConfig, bcache, pos):
    h = layers.rms_norm(x, p["ln1"])
    if _is_attn(kind):
        local = kind == ATTN_LOCAL
        if local:
            # ring-buffer cache: slot = pos % window (absolute-RoPE keys).
            w = bcache["k"].shape[1]
            slot = pos % w
            out, ck, cv = layers.decode_attention_ring(
                p["attn"], h, cfg, bcache["k"], bcache["v"], pos, slot)
        else:
            out, ck, cv = layers.decode_attention(
                p["attn"], h, cfg, bcache["k"], bcache["v"], pos, local=False)
        x = x + out
        h2 = layers.rms_norm(x, p["ln2"])
        if "moe" in p:
            h2 = moe.moe_block(p["moe"], h2, cfg)
        else:
            h2 = layers.mlp_block(p["mlp"], h2, cfg)
        return x + h2, {"k": ck, "v": cv}
    if kind == MAMBA1:
        out, hh, conv = ssm.mamba1_decode(p["ssm"], h, cfg, bcache["h"],
                                          bcache["conv"])
        return x + out, {"h": hh, "conv": conv}
    out, hh, conv = ssm.mamba2_decode(p["ssm"], h, cfg, bcache["h"],
                                      bcache["conv"])
    return x + out, {"h": hh, "conv": conv}


def decode_step(params, cfg: ArchConfig, tokens, cache, pos):
    """tokens: (B, 1) int32; pos: scalar int32 (next position).
    Returns (logits (B, V) fp32, new cache)."""
    scale = jnp.asarray(cfg.d_model ** 0.5, DTYPE)
    x = layers.embed(params["embed"], tokens) * scale
    group, n_groups, rem = cfg.scan_groups()
    shared = params.get("shared")

    if n_groups > 0:
        def body(xc, gp_and_cache):
            gp, gc = gp_and_cache
            new_gc = {}
            for i, kind in enumerate(group):
                p = shared if kind == SHARED_ATTN else gp[f"b{i}"]
                xc, new_gc[f"b{i}"] = _decode_block(p, kind, xc, cfg,
                                                    gc[f"b{i}"], pos)
            return xc, new_gc
        x, new_groups = jax.lax.scan(
            body, x, (params["groups"], cache["groups"]))
        new_cache = {"groups": new_groups}
    else:
        new_cache = {}
    for i, kind in enumerate(rem):
        p = shared if kind == SHARED_ATTN else params[f"rem{i}"]
        x, new_cache[f"rem{i}"] = _decode_block(p, kind, x, cfg,
                                                cache[f"rem{i}"], pos)
    x = layers.rms_norm(x, params["final_ln"])
    logits = layers.unembed_logits(params["embed"], x)[:, 0]
    return logits, new_cache


def resume_supported(cfg: ArchConfig) -> bool:
    """True when the prefix-cache resume path can serve this arch: every
    layer's decode state must be reconstructible from per-position KV
    (attention only).  SSM/hybrid recurrent states fold the whole prefix
    into one vector and cannot be restored from chunk slabs."""
    return all(k in (ATTN_GLOBAL, ATTN_LOCAL) for k in cfg.layer_pattern())


def prefix_length(prefix_kv) -> int:
    """Token length P of a ``prefix_kv`` pytree (as returned by
    ``prefill(..., return_kv=True)``: the sequence axis is always the
    third-from-last — (..., S, KV_heads, d_head))."""
    leaf = jax.tree.leaves(prefix_kv)[0]
    return leaf.shape[leaf.ndim - 3]


def prefill(params, cfg: ArchConfig, batch, max_seq: int, *,
            prefix_kv=None, return_kv: bool = False):
    """Run the trunk over a prompt and build the decode cache.
    Returns (last-token logits (B, V), cache) — plus a per-layer KV
    pytree for the tokens of THIS call when ``return_kv=True``.

    ``prefix_kv`` resumes from a cached prefix: a pytree mirroring the
    cache layout with post-RoPE k/v of the first P prompt tokens (seq
    axis third-from-last).  ``batch`` then holds only the suffix; its
    positions start at P (RoPE offset contract: resumed tokens attend at
    their original absolute positions), attention runs over
    concat(prefix, suffix) with ``q_offset=P``, and the cache is built
    over the combined sequence — bit-identical to a full prefill of the
    whole prompt, since the slabs hold exactly the k/v a full prefill
    would compute."""
    if prefix_kv is not None and not resume_supported(cfg):
        raise NotImplementedError(
            f"prefix resume needs attention-only layers; {cfg.name} "
            "has recurrent (SSM) state that chunk slabs cannot restore")
    x, positions = _input_embeds(params, cfg, batch)
    b, s, _ = x.shape
    p_len = 0
    if prefix_kv is not None:
        p_len = prefix_length(prefix_kv)
        positions = positions + jnp.int32(p_len)
    group, n_groups, rem = cfg.scan_groups()
    shared = params.get("shared")

    def fill_block(p, kind, xc, bcache, pk):
        h = layers.rms_norm(xc, p["ln1"])
        if _is_attn(kind):
            local = kind == ATTN_LOCAL
            q, k, v = layers._qkv(p["attn"], h, cfg, positions)
            q = layers._seq_shard(q, cfg)
            k = layers._seq_shard(k, cfg)
            v = layers._seq_shard(v, cfg)
            if pk is not None:
                # k/v over the COMBINED sequence: cached prefix ++ new.
                k_all = jnp.concatenate([pk["k"].astype(k.dtype), k], axis=1)
                v_all = jnp.concatenate([pk["v"].astype(v.dtype), v], axis=1)
            else:
                k_all, v_all = k, v
            s_tot = k_all.shape[1]
            out = layers.chunked_attention(
                q, k_all, v_all, causal=cfg.causal and not cfg.encoder_only,
                window=cfg.sliding_window if local else 0,
                softcap=cfg.logit_softcap, q_offset=p_len)
            out = out.reshape(b, s, -1) @ p["attn"]["wo"]
            xc = xc + out
            h2 = layers.rms_norm(xc, p["ln2"])
            h2 = (moe.moe_block(p["moe"], h2, cfg) if "moe" in p
                  else layers.mlp_block(p["mlp"], h2, cfg))
            xc = xc + h2
            # write cache (ring layout for local, plain for global) over
            # the combined sequence — same formulas as a full prefill of
            # s_tot tokens.
            cw = bcache["k"].shape[1]
            if local:
                take = min(cw, s_tot)
                ks, vs = k_all[:, -take:], v_all[:, -take:]
                slots = (jnp.arange(s_tot - take, s_tot) % cw).astype(jnp.int32)
                ck = bcache["k"].at[:, slots].set(ks.astype(DTYPE))
                cv = bcache["v"].at[:, slots].set(vs.astype(DTYPE))
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    bcache["k"], k_all.astype(DTYPE), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    bcache["v"], v_all.astype(DTYPE), 0, axis=1)
            kv = {"k": k.astype(DTYPE), "v": v.astype(DTYPE)}
            return xc, {"k": ck, "v": cv}, kv
        # SSM prefill: the chunked block already carries the recurrent state
        # across chunks; return_state hands back (h_final, conv tail) to
        # seed decode exactly.
        fn = ssm.mamba1_block if kind == MAMBA1 else ssm.mamba2_block
        out, h_final, conv_tail = fn(p["ssm"], h, cfg, return_state=True)
        return xc + out, {"h": h_final, "conv": conv_tail}, None

    cache = init_cache(cfg, b, max_seq)
    kv_out = {}
    if n_groups > 0:
        pk_groups = None if prefix_kv is None else prefix_kv["groups"]
        def body(xc, scanned):
            gp, gc, gpk = scanned
            new_gc, new_kv = {}, {}
            for i, kind in enumerate(group):
                p = shared if kind == SHARED_ATTN else gp[f"b{i}"]
                bpk = None if gpk is None else gpk[f"b{i}"]
                xc, new_gc[f"b{i}"], new_kv[f"b{i}"] = fill_block(
                    p, kind, xc, gc[f"b{i}"], bpk)
            return xc, (new_gc, new_kv)
        x, (new_groups, kv_groups) = jax.lax.scan(
            jax.checkpoint(body), x,
            (params["groups"], cache["groups"], pk_groups))
        cache = dict(cache, groups=new_groups)
        kv_out["groups"] = kv_groups
    for i, kind in enumerate(rem):
        p = shared if kind == SHARED_ATTN else params[f"rem{i}"]
        rpk = None if prefix_kv is None else prefix_kv.get(f"rem{i}")
        x, cache[f"rem{i}"], kv_out[f"rem{i}"] = fill_block(
            p, kind, x, cache[f"rem{i}"], rpk)
    x = layers.rms_norm(x, params["final_ln"])
    logits = layers.unembed_logits(params["embed"], x[:, -1:])[:, 0]
    if return_kv:
        return logits, cache, kv_out
    return logits, cache
