"""Jit'd wrappers for the hopscotch window-lookup kernel and the
device-resident insert/delete path (windowed scatter with the hop-chain
displacement as a bounded ``lax.while_loop``)."""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.common import bucket_pow2
from repro.kernels.hopscotch.kernel import BLOCK_Q, hopscotch_lookup_pallas
from repro.kernels.hopscotch.ref import hopscotch_lookup_ref


def hopscotch_lookup(table_lo, table_hi, homes, q_lo, q_hi, *, window: int,
                     block_q: int | None = None,
                     use_kernel: bool = True,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Batched hopscotch window probe over a packed 64-bit key table.

    Parameters
    ----------
    table_lo, table_hi : (N,) uint32
        Low/high halves of the table's 64-bit keys (0 = EMPTY sentinel).
    homes : (Q,) int32
        Home slot of each query (bucket base the H-window starts at).
    q_lo, q_hi : (Q,) uint32
        Low/high halves of the 64-bit query keys.
    window : int
        Hopscotch neighborhood size H (entries scanned per query).
    block_q : int, optional
        Queries per kernel grid step (default 8); each step gather-DMAs
        all its queries' window tiles together.
    use_kernel, interpret
        Reference-path switch and Pallas interpret-mode flag (None:
        compiled on a TPU, interpreted on the CPU).

    Returns
    -------
    jnp.ndarray, shape (Q,), int32
        First-match offset within each query's window; ``-1`` = miss.

    Notes
    -----
    The query count is bucketed to a power of two HERE, on the host, so
    ragged batches reuse a handful of compiled shapes (the jitted kernel
    specializes on its input shapes).
    """
    table_lo = jnp.asarray(table_lo, jnp.uint32)
    table_hi = jnp.asarray(table_hi, jnp.uint32)
    homes = jnp.asarray(homes, jnp.int32)
    q_lo = jnp.asarray(q_lo, jnp.uint32)
    q_hi = jnp.asarray(q_hi, jnp.uint32)
    if not use_kernel:
        return hopscotch_lookup_ref(table_lo, table_hi, homes, q_lo, q_hi, window)
    if block_q is None:
        block_q = BLOCK_Q
    q = homes.shape[0]
    qp = bucket_pow2(q, block_q)
    if qp != q:
        # pad rows carry home 0 / key 0 and are sliced off below
        pad = np.zeros(qp - q, np.int32)
        homes = jnp.concatenate([homes, jnp.asarray(pad)])
        q_lo = jnp.concatenate([q_lo, jnp.asarray(pad.view(np.uint32))])
        q_hi = jnp.concatenate([q_hi, jnp.asarray(pad.view(np.uint32))])
    out = hopscotch_lookup_pallas(
        table_lo, table_hi, homes, q_lo, q_hi,
        window=window, block_q=block_q, interpret=interpret)
    return out[:q]


# ---------------------------------------------------------------------------
# Device-resident mutation path (apps/hashtable.py "device" backend).
# ---------------------------------------------------------------------------

def _murmur3_u32(x: jnp.ndarray) -> jnp.ndarray:
    """jnp twin of ``repro.data.pipeline.murmur3_np`` (32-bit finalizer);
    uint32 multiplies wrap, which is the point."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


@functools.partial(jax.jit, static_argnames=("window",),
                   donate_argnums=(0, 1, 2, 3))
def hopscotch_insert_device(k_lo, k_hi, v_lo, v_hi, home, q_lo, q_hi,
                            nv_lo, nv_hi, *, window: int):
    """One hopscotch insert, entirely on device (donated planes).

    Bit-for-bit replica of ``HopscotchTable.insert``'s host algorithm over
    the split uint32 key/value planes (length ``n + 2*window``; 0/0 =
    EMPTY): resident-key value update, first-free-window install, else
    forward walk to the first free bucket (vectorized mask scan, capped at
    ``min(n + w, home + 64w)``) and hop-chain displacement back into the
    window as a bounded ``lax.while_loop`` — each hop moves the FIRST
    window-compatible key forward (its home recomputed on device with
    ``_murmur3_u32``, matching the host hash low-word-exactly), exactly
    like the host's inner ``for`` scan, and a failed chain leaves partial
    moves in place for the host-orchestrated rehash.

    Returns
    -------
    (k_lo, k_hi, v_lo, v_hi, status, probes, swaps, log, n_log)
        Updated planes; ``status`` 0 = resident value update, 1 =
        installed, 2 = needs rehash; ``probes`` the ``insert_probes``
        delta; ``swaps`` the hop count; ``log[:n_log]`` the touched
        bucket indices in the host's exact ``_record_write`` order
        (j, k per hop, then the final install slot) so wear accounting
        replays identically.
    """
    w = window
    n_pad = k_lo.shape[0]
    n = n_pad - 2 * w
    h = home.astype(jnp.int32)
    iota = jnp.arange(n_pad, dtype=jnp.int32)
    log_cap = 128 * w            # > 2 * 63w hop writes + 1 final install

    wk_lo = lax.dynamic_slice(k_lo, (h,), (w,))
    wk_hi = lax.dynamic_slice(k_hi, (h,), (w,))
    hit = (wk_lo == q_lo) & (wk_hi == q_hi)
    is_res = jnp.any(hit)
    res_off = jnp.argmax(hit).astype(jnp.int32)

    empty_w = (wk_lo == 0) & (wk_hi == 0)
    has_free = jnp.any(empty_w)
    free_off = jnp.argmax(empty_w).astype(jnp.int32)
    do_freewin = ~is_res & has_free
    need_hop = ~is_res & ~has_free

    # Forward walk: first free bucket past the window, as one mask scan.
    occ = (k_lo != 0) | (k_hi != 0)
    limit = jnp.minimum(jnp.int32(n + w), h + 64 * w)
    cand = ~occ & (iota >= h + w) & (iota < limit)
    fwd_found = jnp.any(cand)
    j0 = jnp.argmax(cand).astype(jnp.int32)
    advances = jnp.where(fwd_found, j0, limit) - (h + w)
    probes = jnp.where(
        is_res, 0, jnp.where(do_freewin, free_off + 1, w + advances))
    hop_ok = need_hop & fwd_found

    log = jnp.full((log_cap,), -1, jnp.int32)
    n_log = jnp.int32(0)

    def cond(c):
        _, _, _, _, _, _, j, failed = c
        return hop_ok & ~failed & (j >= h + w)

    def body(c):
        k_lo, k_hi, v_lo, v_hi, log, nl, j, failed = c
        c_lo = lax.dynamic_slice(k_lo, (j - w + 1,), (w - 1,))
        c_hi = lax.dynamic_slice(k_hi, (j - w + 1,), (w - 1,))
        occ_k = (c_lo != 0) | (c_hi != 0)
        homes_k = (_murmur3_u32(c_lo) % jnp.uint32(n)).astype(jnp.int32)
        movable = occ_k & (j < homes_k + w)
        any_mv = jnp.any(movable)
        k = j - w + 1 + jnp.argmax(movable).astype(jnp.int32)
        jj = jnp.where(any_mv, j, n_pad)      # sentinel: drop when no move
        kk = jnp.where(any_mv, k, n_pad)
        # move k -> j: keys clear at k, values keep the host's stale copy
        k_lo = k_lo.at[jj].set(k_lo[k], mode="drop").at[kk].set(
            jnp.uint32(0), mode="drop")
        k_hi = k_hi.at[jj].set(k_hi[k], mode="drop").at[kk].set(
            jnp.uint32(0), mode="drop")
        v_lo = v_lo.at[jj].set(v_lo[k], mode="drop")
        v_hi = v_hi.at[jj].set(v_hi[k], mode="drop")
        log = log.at[jnp.where(any_mv, nl, log_cap)].set(j, mode="drop")
        log = log.at[jnp.where(any_mv, nl + 1, log_cap)].set(k, mode="drop")
        nl = nl + jnp.where(any_mv, 2, 0)
        j = jnp.where(any_mv, k, j)
        return (k_lo, k_hi, v_lo, v_hi, log, nl, j, failed | ~any_mv)

    if w > 1:
        (k_lo, k_hi, v_lo, v_hi, log, n_log, j_fin, failed) = lax.while_loop(
            cond, body, (k_lo, k_hi, v_lo, v_hi, log, n_log, j0, False))
    else:   # degenerate window: no hop candidates exist, chain always fails
        j_fin, failed = j0, hop_ok
    swaps = n_log // 2
    installed_hop = hop_ok & ~failed

    slot = jnp.where(is_res, h + res_off,
                     jnp.where(do_freewin, h + free_off, j_fin))
    put_key = do_freewin | installed_hop
    put_val = put_key | is_res
    ki = jnp.where(put_key, slot, n_pad)
    vi = jnp.where(put_val, slot, n_pad)
    k_lo = k_lo.at[ki].set(q_lo, mode="drop")
    k_hi = k_hi.at[ki].set(q_hi, mode="drop")
    v_lo = v_lo.at[vi].set(nv_lo, mode="drop")
    v_hi = v_hi.at[vi].set(nv_hi, mode="drop")
    log = log.at[jnp.where(put_val, n_log, log_cap)].set(slot, mode="drop")
    n_log = n_log + put_val.astype(jnp.int32)

    status = jnp.where(
        is_res, 0, jnp.where(do_freewin | installed_hop, 1, 2)
    ).astype(jnp.int32)
    return k_lo, k_hi, v_lo, v_hi, status, probes, swaps, log, n_log


@jax.jit
def hopscotch_delete_device(k_lo, k_hi, v_lo, v_hi, idx):
    """Clear one resolved bucket (key AND value planes) on device.

    The caller resolves ``idx`` via the window lookup; donation is left
    OFF so a miss path can reuse the planes untouched."""
    return (k_lo.at[idx].set(jnp.uint32(0)),
            k_hi.at[idx].set(jnp.uint32(0)),
            v_lo.at[idx].set(jnp.uint32(0)),
            v_hi.at[idx].set(jnp.uint32(0)))
