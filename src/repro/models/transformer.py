"""Model assembly: composable blocks -> scan-over-layer-groups stack.

The per-layer pattern of each architecture (dense / 5:1 local:global /
MoE / Mamba / hybrid-with-shared-attention / Mamba-2 with periodic
attention) is factored into a repeating
*group* that is scanned with stacked parameters (plus an unscanned
remainder), so HLO size and compile time are independent of depth — a 62
layer model lowers as one group body.

Public entry points (used by train/serve/launch):

    init_params(key, cfg)                      -> params pytree
    forward(params, cfg, batch)                -> final hidden states
    train_loss(params, cfg, batch)             -> scalar CE loss
    init_cache(cfg, batch, max_seq)            -> decode cache pytree
    prefill(params, cfg, batch, max_seq)       -> (last-token logits, cache)
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
    elif kind == MAMBA1:
        p["ssm"] = ssm.init_mamba1(k[0], cfg)
    elif kind == MAMBA2:
        p["ssm"] = ssm.init_mamba2(k[0], cfg)
    else:
        raise ValueError(kind)
    if _is_attn(kind) or cfg.ssm_mlp:
        p["ln2"] = jnp.zeros((cfg.d_model,), DTYPE)
        if cfg.n_experts and kind != SHARED_ATTN:
            p["moe"] = moe.init_moe(k[1], cfg)
        else:
            p["mlp"] = layers.init_mlp(k[1], cfg)
    return p


def _norm(x, w, cfg: ArchConfig):
    return layers.rms_norm(x, w, cfg.rms_norm_eps)


def _residual(x, h, cfg: ArchConfig):
    """x + h, the branch scaled by the published residual multiplier (in
    fp32: the multiplier itself is not rounded to bf16)."""
    if cfg.residual_multiplier != 1.0:
        h = (h.astype(jnp.float32) * cfg.residual_multiplier).astype(h.dtype)
    return x + h


def _ffn(p, x, cfg: ArchConfig):
    """The block's MLP (or MoE) sub-block after its mixer, if it has one."""
    if "ln2" not in p:
        return x
    h = _norm(x, p["ln2"], cfg)
    h = (moe.moe_block(p["moe"], h, cfg) if "moe" in p
         else layers.mlp_block(p["mlp"], h, cfg))
    return _residual(x, h, cfg)


def apply_block(p, kind: str, x, cfg: ArchConfig, positions):
    h = _norm(x, p["ln1"], cfg)
    if _is_attn(kind):
        h = layers.attention_block(p["attn"], h, cfg, positions,
                                   local=(kind == ATTN_LOCAL))
    else:
        fn = ssm.mamba1_block if kind == MAMBA1 else ssm.mamba2_block
        h = fn(p["ssm"], h, cfg)
    return _ffn(p, _residual(x, h, cfg), cfg)


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

def _embed_scale(cfg: ArchConfig):
    """The published ``embedding_multiplier``, else sqrt(d_model)."""
    m = cfg.embedding_multiplier
    return jnp.asarray(cfg.d_model ** 0.5 if m is None else m, DTYPE)


def _logits(params, cfg: ArchConfig, x):
    """Output-head logits (fp32), divided by ``logits_scaling``."""
    logits = layers.unembed_logits(params["embed"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _input_embeds(params, cfg: ArchConfig, batch):
    parts = []
    if "embeds" in batch:
        parts.append(batch["embeds"].astype(DTYPE))
    if "tokens" in batch:
        parts.append(layers.embed(params["embed"], batch["tokens"])
                     * _embed_scale(cfg))
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
    return _norm(x, params["final_ln"], cfg)


def train_loss(params, cfg: ArchConfig, batch):
    x = forward(params, cfg, batch)
    labels = batch["labels"]
    if "embeds" in batch and "tokens" in batch:
        # VLM: loss only over the text tail (prefix embeds carry no labels).
        x = x[:, batch["embeds"].shape[1]:]
    loss = layers.chunked_ce_loss(params["embed"], x, labels,
                                  logits_scaling=cfg.logits_scaling)
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
    h = _norm(x, p["ln1"], cfg)
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
        new = {"k": ck, "v": cv}
    else:
        fn = ssm.mamba1_decode if kind == MAMBA1 else ssm.mamba2_decode
        out, hh, conv = fn(p["ssm"], h, cfg, bcache["h"], bcache["conv"])
        new = {"h": hh, "conv": conv}
    return _ffn(p, _residual(x, out, cfg), cfg), new


def decode_step(params, cfg: ArchConfig, tokens, cache, pos):
    """tokens: (B, 1) int32; pos: scalar int32 (next position).
    Returns (logits (B, V) fp32, new cache)."""
    x = layers.embed(params["embed"], tokens) * _embed_scale(cfg)
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
    x = _norm(x, params["final_ln"], cfg)
    logits = _logits(params, cfg, x)[:, 0]
    return logits, new_cache


def resume_blocker(cfg: ArchConfig) -> str | None:
    """Why the prefix-cache resume path cannot serve this arch, or None
    when it can.  Attention layers resume from per-position KV; Mamba-2
    layers resume from a snapshot of their recurrent state and conv tail
    taken at one chunk boundary of the SSD (``prefill(snapshot_at=)``).
    Other recurrent layers have no snapshot."""
    kinds = set(cfg.layer_pattern())
    if MAMBA1 in kinds:
        return ("its Mamba-1 layers have no state snapshot (only the "
                "Mamba-2 SSD returns its state at a chunk boundary)")
    if SHARED_ATTN in kinds:
        return ("its shared attention block (one set of weights reused "
                "at several depths, zamba2) has no resume path")
    return None


def resume_supported(cfg: ArchConfig) -> bool:
    """True when the prefix-cache resume path can serve this arch
    (:func:`resume_blocker` says why not)."""
    return resume_blocker(cfg) is None


def has_recurrent_state(cfg: ArchConfig) -> bool:
    """True when some layer folds the prefix into a recurrent state, so a
    resume needs a state snapshot besides the attention KV."""
    return any(k in (MAMBA1, MAMBA2) for k in cfg.layer_pattern())


def prefix_length(prefix_kv) -> int:
    """Token length P of a ``prefix_kv`` pytree (as returned by
    ``prefill(..., return_kv=True)``: the sequence axis is always the
    third-from-last — (..., S, KV_heads, d_head))."""
    leaf = jax.tree.leaves(prefix_kv)[0]
    return leaf.shape[leaf.ndim - 3]


def prefill(params, cfg: ArchConfig, batch, max_seq: int, *,
            prefix_kv=None, prefix_state=None, return_kv: bool = False,
            snapshot_at: int | None = None):
    """Run the trunk over a prompt and build the decode cache.
    Returns (last-token logits (B, V), cache) — plus a per-layer KV
    pytree for the tokens of THIS call when ``return_kv=True``, plus
    the recurrent state at token ``snapshot_at`` when that is given.

    ``prefix_kv`` resumes from a cached prefix: a pytree mirroring the
    cache layout with post-RoPE k/v of the first P prompt tokens (seq
    axis third-from-last).  ``batch`` then holds only the suffix; its
    positions start at P (RoPE offset contract: resumed tokens attend at
    their original absolute positions), attention runs over
    concat(prefix, suffix) with ``q_offset=P``, and the cache is built
    over the combined sequence — bit-identical to a full prefill of the
    whole prompt, since the slabs hold exactly the k/v a full prefill
    would compute.

    Mamba-2 layers resume from ``prefix_state``, a pytree mirroring the
    cache layout with each Mamba-2 layer's {"h": fp32 state, "conv":
    conv tail} after the first P tokens (None at attention layers).
    ``snapshot_at`` (an absolute token position, P < snapshot_at <= P +
    suffix, on a ``cfg.ssm_chunk`` boundary counted from P) returns the
    same pytree for the state after that token: the snapshot a later
    prefill resumes from."""
    blocker = resume_blocker(cfg)
    if prefix_kv is not None and blocker is not None:
        raise NotImplementedError(
            f"prefix resume cannot serve {cfg.name}: {blocker}")
    x, positions = _input_embeds(params, cfg, batch)
    b, s, _ = x.shape
    p_len = 0
    if prefix_kv is not None:
        p_len = prefix_length(prefix_kv)
        positions = positions + jnp.int32(p_len)
    if has_recurrent_state(cfg) and (prefix_kv is None) != (prefix_state is None):
        raise ValueError("a resumed prefill of a recurrent arch needs both "
                         "the prefix KV and the prefix state")
    snap = None if snapshot_at is None else snapshot_at - p_len
    group, n_groups, rem = cfg.scan_groups()
    shared = params.get("shared")

    def fill_block(p, kind, xc, bcache, pk, ps):
        h = _norm(xc, p["ln1"], cfg)
        if not _is_attn(kind):
            # SSM prefill: the chunked block carries the recurrent state
            # across chunks and returns (h_final, conv tail) to seed decode
            # exactly; a resumed Mamba-2 layer starts from ``ps``.
            if kind == MAMBA1:
                out, h_final, conv_tail = ssm.mamba1_block(
                    p["ssm"], h, cfg, return_state=True)
                snap_state = None
            else:
                res = ssm.mamba2_block(
                    p["ssm"], h, cfg, return_state=True, snap_at=snap,
                    h0=None if ps is None else ps["h"],
                    conv0=None if ps is None else ps["conv"])
                out, h_final, conv_tail = res[:3]
                snap_state = (None if snap is None
                              else {"h": res[3], "conv": res[4]})
            xc = _ffn(p, _residual(xc, out, cfg), cfg)
            return xc, {"h": h_final, "conv": conv_tail}, None, snap_state
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
            softcap=cfg.logit_softcap, q_offset=p_len,
            kv_chunk=cfg.attn_kv_chunk, scale=layers.attn_scale(cfg))
        out = out.reshape(b, s, -1) @ p["attn"]["wo"]
        xc = _ffn(p, _residual(xc, out, cfg), cfg)
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
        return xc, {"k": ck, "v": cv}, kv, None

    cache = init_cache(cfg, b, max_seq)
    kv_out, snap_out = {}, {}

    def part(tree, key):
        return None if tree is None else tree.get(key)

    if n_groups > 0:
        def body(xc, scanned):
            gp, gc, gpk, gps = scanned
            new_gc, new_kv, new_snap = {}, {}, {}
            for i, kind in enumerate(group):
                p = shared if kind == SHARED_ATTN else gp[f"b{i}"]
                xc, new_gc[f"b{i}"], new_kv[f"b{i}"], new_snap[f"b{i}"] = \
                    fill_block(p, kind, xc, gc[f"b{i}"], part(gpk, f"b{i}"),
                               part(gps, f"b{i}"))
            return xc, (new_gc, new_kv, new_snap)
        x, (new_groups, kv_groups, snap_groups) = jax.lax.scan(
            jax.checkpoint(body), x,
            (params["groups"], cache["groups"], part(prefix_kv, "groups"),
             part(prefix_state, "groups")))
        cache = dict(cache, groups=new_groups)
        kv_out["groups"] = kv_groups
        snap_out["groups"] = snap_groups
    for i, kind in enumerate(rem):
        p = shared if kind == SHARED_ATTN else params[f"rem{i}"]
        x, cache[f"rem{i}"], kv_out[f"rem{i}"], snap_out[f"rem{i}"] = \
            fill_block(p, kind, x, cache[f"rem{i}"],
                       part(prefix_kv, f"rem{i}"),
                       part(prefix_state, f"rem{i}"))
    x = _norm(x, params["final_ln"], cfg)
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    out = (logits, cache)
    if return_kv:
        out += (kv_out,)
    if snapshot_at is not None:
        out += (snap_out,)
    return out
