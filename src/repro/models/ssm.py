"""State-space blocks: Mamba-1 (selective scan) and Mamba-2 (SSD, chunked).

Both are written chunk-parallel over the sequence with rematerialized chunk
bodies: the (B, L, d_inner, N) discretized tensors exist only per-chunk, so
32k/500k sequences never materialize full scan residuals (this is the
sub-quadratic long-context path for falcon-mamba / zamba2 / long_500k).

Sharding intent (see repro.dist.sharding): d_inner (mamba1) and heads
(mamba2) shard over the `model` mesh axis; batch over (`pod`, `data`).
The SSM recurrence itself is purely local to those shards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers

DTYPE = layers.DTYPE


# ---------------------------------------------------------------------------
# Mamba-1 (falcon-mamba-7b: d_state=16, expand=2, conv=4, dt_rank=D/16).
# ---------------------------------------------------------------------------

def dt_rank(cfg: ArchConfig) -> int:
    return max(cfg.d_model // 16, 1)


def d_inner(cfg: ArchConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def init_mamba1(key, cfg: ArchConfig):
    di, n, r = d_inner(cfg), cfg.ssm_state, dt_rank(cfg)
    k = layers.split_keys(key, 7)
    # S4D-real initialization for A.
    a_init = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "wx": layers.dense_init(k[0], (cfg.d_model, di)),
        "wz": layers.dense_init(k[5], (cfg.d_model, di)),
        "conv_w": layers.dense_init(k[1], (cfg.ssm_conv, di), scale=0.5),
        "conv_b": jnp.zeros((di,), DTYPE),
        "x_proj": layers.dense_init(k[2], (di, r + 2 * n)),
        "dt_w": layers.dense_init(k[3], (r, di)),
        "dt_b": (jnp.log(jnp.expm1(jnp.full((di,), 0.01)))).astype(DTYPE),
        "a_log": jnp.log(a_init),                    # (di, n) fp32
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": layers.dense_init(k[4], (di, cfg.d_model)),
    }


def _causal_depthwise_conv(x, w, b, tail=None):
    """x: (B, S, C); w: (K, C) — causal per-channel conv, unrolled taps.
    ``tail`` (B, K-1, C) holds the inputs before x[0] (zeros if None)."""
    k = w.shape[0]
    xp = (jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))) if tail is None
          else jnp.concatenate([tail.astype(x.dtype), x], axis=1))
    s = x.shape[1]
    acc = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        acc = acc + xp[:, i:i + s, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (acc + b.astype(jnp.float32)).astype(x.dtype)


def mamba1_block(params, x, cfg: ArchConfig, *, chunk: int = 64,
                 return_state: bool = False):
    """x: (B, S, D) -> (B, S, D) via chunked selective scan.
    With ``return_state``: also returns (h_final, conv_tail) for prefill."""
    b, s, _ = x.shape
    di, n, r = d_inner(cfg), cfg.ssm_state, dt_rank(cfg)
    xh_raw = x @ params["wx"]                        # (B, S, di)
    z = x @ params["wz"]
    xh = xh_raw
    xh = jax.nn.silu(_causal_depthwise_conv(xh, params["conv_w"], params["conv_b"]))

    dbc = xh @ params["x_proj"]                      # (B, S, r + 2n)
    dt_in, b_in, c_in = jnp.split(dbc, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dt_in @ params["dt_w"] +
                         params["dt_b"].astype(jnp.float32))  # (B,S,di) fp32
    a = -jnp.exp(params["a_log"])                    # (di, n)

    chunk = min(chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    pad = n_chunks * chunk - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))

    def chunk_body(h, idx):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, idx * chunk, chunk, 1)
        xc, dtc = sl(xh), sl(dt)
        bc, cc = sl(b_in).astype(jnp.float32), sl(c_in).astype(jnp.float32)
        # per-step discretization, sequential within chunk.
        dA = jnp.exp(dtc[..., None] * a[None, None])           # (B,C,di,n)
        dBx = (dtc * xc.astype(jnp.float32))[..., None] * bc[:, :, None, :]

        def step(hc, t):
            hc = hc * dA[:, t] + dBx[:, t]                     # (B, di, n)
            y_t = jnp.einsum("bdn,bn->bd", hc, cc[:, t])
            return hc, y_t

        h, ys = jax.lax.scan(step, h, jnp.arange(chunk))
        return h, jnp.moveaxis(ys, 0, 1)                       # (B, C, di)

    h0 = jnp.zeros((b, di, n), jnp.float32)
    h_final, y = jax.lax.scan(jax.checkpoint(chunk_body), h0, jnp.arange(n_chunks))
    y = y.transpose(1, 0, 2, 3).reshape(b, n_chunks * chunk, di)[:, :s]
    y = y + xh[:, :s].astype(jnp.float32) * params["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ params["out_proj"]
    if return_state:
        k = cfg.ssm_conv
        tail = jnp.pad(xh_raw, ((0, 0), (k - 1, 0), (0, 0)))[:, s:s + k - 1]
        return out, h_final, tail.astype(DTYPE)
    return out


def _conv_step(x_t, conv_buf, w, b):
    """One causal depthwise-conv step with a (B, K-1, C) ring buffer.
    Returns (conv_out (B, C), new_buf)."""
    ext = jnp.concatenate([conv_buf, x_t[:, None, :]], axis=1)  # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", ext.astype(jnp.float32),
                     w.astype(jnp.float32)) + b.astype(jnp.float32)
    return out, ext[:, 1:, :]


def mamba1_decode(params, x, cfg: ArchConfig, h, conv_buf):
    """Single-token step.  x: (B, 1, D); h: (B, di, n) fp32 state;
    conv_buf: (B, K-1, di) tap ring buffer.
    Returns (out (B,1,D), new_h, new_conv_buf)."""
    b = x.shape[0]
    di, n, r = d_inner(cfg), cfg.ssm_state, dt_rank(cfg)
    xh = x[:, 0] @ params["wx"]
    z = x[:, 0] @ params["wz"]
    xh_c, conv_buf = _conv_step(xh, conv_buf, params["conv_w"], params["conv_b"])
    xh = jax.nn.silu(xh_c).astype(x.dtype)
    dbc = xh @ params["x_proj"]
    dt_in, b_in, c_in = jnp.split(dbc, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dt_in @ params["dt_w"] + params["dt_b"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"])
    dA = jnp.exp(dt[..., None] * a[None])                     # (B, di, n)
    dBx = (dt * xh.astype(jnp.float32))[..., None] * b_in.astype(jnp.float32)[:, None, :]
    h = h * dA + dBx
    y = jnp.einsum("bdn,bn->bd", h, c_in.astype(jnp.float32))
    y = y + xh.astype(jnp.float32) * params["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return (y @ params["out_proj"])[:, None, :], h, conv_buf


# ---------------------------------------------------------------------------
# Mamba-2 / SSD (zamba2: d_state=64, headdim=64, scalar A per head).
# ---------------------------------------------------------------------------

def m2_heads(cfg: ArchConfig) -> int:
    return d_inner(cfg) // cfg.ssm_head_dim


def init_mamba2(key, cfg: ArchConfig):
    di, n, h = d_inner(cfg), cfg.ssm_state, m2_heads(cfg)
    k = layers.split_keys(key, 4)
    # separate projections (clean tensor-parallel sharding; a fused
    # in_proj would put split boundaries mid-shard):
    return {
        "wz": layers.dense_init(k[0], (cfg.d_model, di)),
        "wxbc": layers.dense_init(k[3], (cfg.d_model, di + 2 * n)),
        "wdt": layers.dense_init(k[1], (cfg.d_model, h), scale=0.02),
        "conv_w": layers.dense_init(k[1], (cfg.ssm_conv, di + 2 * n), scale=0.5),
        "conv_b": jnp.zeros((di + 2 * n,), DTYPE),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        # Mamba-2's dt range [0.001, 0.1], spread over the heads: the
        # slowest keep a state over about a thousand tokens.
        "dt_b": jnp.log(jnp.expm1(jnp.geomspace(0.001, 0.1, h))
                        ).astype(jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.zeros((di,), DTYPE),
        "out_proj": layers.dense_init(k[2], (di, cfg.d_model)),
    }


def mamba2_block(params, x, cfg: ArchConfig, *, h0=None, conv0=None,
                 return_state: bool = False, snap_at: int | None = None):
    """SSD forward, chunked (Mamba-2 minimal algorithm) in chunks of
    ``cfg.ssm_chunk`` tokens.  x: (B,S,D).

    ``h0`` (B, H, P, N) fp32 and ``conv0`` (B, K-1, di + 2n) start the
    block mid-sequence, from the state and conv tail a previous call
    returned (zeros when None).  With ``return_state``: also returns
    (h_final, conv_tail) for prefill.  With ``snap_at`` (a token offset,
    a multiple of the chunk): the returns gain the (state, conv tail)
    after the first ``snap_at`` tokens, taken between the chunk scans,
    so no state is kept at any other boundary."""
    bsz, s, _ = x.shape
    di, n, h = d_inner(cfg), cfg.ssm_state, m2_heads(cfg)
    p = cfg.ssm_head_dim

    z = x @ params["wz"]
    xbc_raw = x @ params["wxbc"]
    dt_in = x @ params["wdt"]
    xbc = jax.nn.silu(_causal_depthwise_conv(xbc_raw, params["conv_w"],
                                             params["conv_b"], conv0))
    xh, b_in, c_in = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_in.astype(jnp.float32) + params["dt_b"])   # (B,S,H)
    a = -jnp.exp(params["a_log"])                                      # (H,)
    log_a = dt * a[None, None, :]                                      # (B,S,H) <= 0

    chunk = min(cfg.ssm_chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    pad = n_chunks * chunk - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))

    xhh = xh.reshape(bsz, n_chunks, chunk, h, p)
    dtc = dt.reshape(bsz, n_chunks, chunk, h)
    la = log_a.reshape(bsz, n_chunks, chunk, h)
    bb = b_in.reshape(bsz, n_chunks, chunk, n).astype(jnp.float32)
    cc = c_in.reshape(bsz, n_chunks, chunk, n).astype(jnp.float32)

    def chunk_body(hstate, idx):
        # hstate: (B, H, P, N) fp32 carried across chunks.
        xc = xhh[:, idx].astype(jnp.float32)       # (B,L,H,P)
        d = dtc[:, idx]                            # (B,L,H)
        l = la[:, idx]                             # (B,L,H)
        bc, ccc = bb[:, idx], cc[:, idx]           # (B,L,N)
        cs = jnp.cumsum(l, axis=1)                 # (B,L,H) inclusive
        # intra-chunk (attention-like) term.
        seg = cs[:, :, None, :] - cs[:, None, :, :]        # (B,L,L,H) log decay i<-j
        iota = jnp.arange(chunk)
        causal = (iota[:, None] >= iota[None, :])
        decay = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bin,bjn->bij", ccc, bc)            # (B,L,L)
        w = cb[:, :, :, None] * decay                       # (B,L,L,H)
        y_diag = jnp.einsum("bijh,bjhp->bihp", w, xc * d[..., None])
        # inter-chunk: contribution of carried state.
        state_decay = jnp.exp(cs)                           # (B,L,H)
        y_off = jnp.einsum("bln,bhpn,blh->blhp", ccc, hstate, state_decay)
        y = y_diag + y_off + xc * params["d_skip"][None, None, :, None]
        # update carried state.
        tail = jnp.exp(cs[:, -1:, :] - cs)                  # (B,L,H) decay to end
        new_state = hstate * jnp.exp(cs[:, -1])[..., None, None]  # (B,H,P,N)
        chunk_state = jnp.einsum("blh,bln,blhp->bhpn", tail * d, bc, xc)
        return new_state + chunk_state, y

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    body = jax.checkpoint(chunk_body)
    if snap_at is None:
        h_final, ys = jax.lax.scan(body, h0, jnp.arange(n_chunks))
    else:
        # Two scans meeting at the snapshot boundary: the state there is
        # the first scan's carry.
        if snap_at % chunk or not 0 <= snap_at <= s:
            raise ValueError(f"snapshot at token {snap_at}: not a chunk "
                             f"boundary of {s} tokens in chunks of {chunk}")
        j = snap_at // chunk
        h_snap, ys_a = jax.lax.scan(body, h0, jnp.arange(j))
        h_final, ys_b = jax.lax.scan(body, h_snap, jnp.arange(j, n_chunks))
        ys = jnp.concatenate([ys_a, ys_b], axis=0)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, n_chunks * chunk, di)[:, :s]
    # gated RMSNorm then out-projection.
    y = layers.rms_norm(y.astype(DTYPE) * jax.nn.silu(z.astype(jnp.float32)).astype(DTYPE),
                        params["norm_w"], cfg.rms_norm_eps)
    out = y @ params["out_proj"]
    if not return_state and snap_at is None:
        return out
    # Conv tails: the K-1 raw inputs before a boundary (token t of this
    # call is row t + K-1 of the extended input).
    k = cfg.ssm_conv
    ext = (jnp.pad(xbc_raw, ((0, 0), (k - 1, 0), (0, 0))) if conv0 is None
           else jnp.concatenate([conv0.astype(xbc_raw.dtype), xbc_raw], 1))
    res = (out,)
    if return_state:
        res += (h_final, ext[:, s:s + k - 1].astype(DTYPE))
    if snap_at is not None:
        res += (h_snap, ext[:, snap_at:snap_at + k - 1].astype(DTYPE))
    return res


def mamba2_decode(params, x, cfg: ArchConfig, hstate, conv_buf):
    """Single-token SSD step.  hstate: (B, H, P, N) fp32;
    conv_buf: (B, K-1, di + 2n)."""
    bsz = x.shape[0]
    di, n, h = d_inner(cfg), cfg.ssm_state, m2_heads(cfg)
    p = cfg.ssm_head_dim
    z = x[:, 0] @ params["wz"]
    xbc = x[:, 0] @ params["wxbc"]
    dt_in = x[:, 0] @ params["wdt"]
    xbc_c, conv_buf = _conv_step(xbc, conv_buf, params["conv_w"], params["conv_b"])
    xbc = jax.nn.silu(xbc_c).astype(x.dtype)
    xh, b_in, c_in = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_in.astype(jnp.float32) + params["dt_b"])   # (B,H)
    a = -jnp.exp(params["a_log"])
    da = jnp.exp(dt * a[None, :])                                      # (B,H)
    xhp = xh.reshape(bsz, h, p).astype(jnp.float32)
    bcf = b_in.astype(jnp.float32)
    hstate = hstate * da[:, :, None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xhp, bcf)
    y = jnp.einsum("bhpn,bn->bhp", hstate, c_in.astype(jnp.float32))
    y = y + xhp * params["d_skip"][None, :, None]
    y = y.reshape(bsz, di)
    y = layers.rms_norm((y[:, None, :].astype(DTYPE)
                         * jax.nn.silu(z.astype(jnp.float32))[:, None, :].astype(DTYPE)),
                        params["norm_w"], cfg.rms_norm_eps)
    return y @ params["out_proj"], hstate, conv_buf
