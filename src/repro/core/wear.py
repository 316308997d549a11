"""Wear-leveling and t_MWW enforcement (paper §8, Fig. 8).

Pure-functional state machine over JAX arrays so it composes into the
``lax.scan`` trace simulator AND is unit/property-testable in isolation.

Components reproduced:

* Superset Write Table (SWT): W (written) and D (dirty) flags per superset.
* write / superset / dirty counters.
* WR approximation WITHOUT a divider: WR = 1 when the most significant
  non-zero bit of the write counter is >= 9 binary orders (512x) above the
  superset counter's MSB.
* rotate = WR | WC | DC  (WC/DC = saturation limits of the counters;
  the paper sets DC = 8192 to bound flush cost).
* On rotate: dirty supersets flushed (returned as a count + mask for the
  simulator to charge writeback traffic), SWT and counters reset, rotary
  offsets bumped by unique primes (geometry.apply_rotate).
* t_MWW: per-superset write budget of 512*M per window (t_MWW enforced at
  superset granularity = 512 blocks, §8 "Tracking Writes"); a superset
  exceeding the budget is locked (cache mode: bypass to main memory) until
  the window rolls over.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import geometry
from repro.core.timing import CPU_HZ, t_mww_seconds


#: Cycle resolution of the ``clock="wall"`` domain: one cycle per
#: microsecond of host wall time.  Chosen so realistic t_MWW windows fit
#: the int32 cycle domain the predicates operate in — the serving rebase
#: (``CLOCK_REBASE_AT``) folds the clock every ~17.9 wall-minutes, which
#: also bounds the longest expressible window.
WALL_HZ = 1_000_000

#: Legal values of the ``clock`` knob.
CLOCKS = ("ops", "wall")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WearConfig:
    n_supersets: int = dataclasses.field(metadata=dict(static=True))
    m_writes: int = dataclasses.field(metadata=dict(static=True), default=3)
    dc_limit: int = dataclasses.field(metadata=dict(static=True), default=8192)
    wc_limit: int = dataclasses.field(metadata=dict(static=True), default=1 << 22)
    wr_shift: int = dataclasses.field(metadata=dict(static=True), default=9)
    t_mww_cycles: int = dataclasses.field(metadata=dict(static=True), default=0)
    blocks_per_superset: int = dataclasses.field(metadata=dict(static=True), default=512)
    #: Cycle DOMAIN of every stamp fed to the window predicates:
    #: ``"ops"`` — the caller's op/request counter stands in for cycles
    #: (the simulator and the serving default; PRE-EXISTING semantics,
    #: bit-identical); ``"wall"`` — stamps are host wall-clock
    #: microseconds (``WALL_HZ``), so ``t_mww_cycles`` expresses a
    #: LATENCY-ERA time budget.  The predicates themselves are
    #: clock-agnostic (pure int32 difference arithmetic, see
    #: ``_window_now``); this field records which domain the caller must
    #: stamp in and steers ``make_config``'s window derivation.
    clock: str = dataclasses.field(metadata=dict(static=True), default="ops")

    def __post_init__(self):
        if self.clock not in CLOCKS:
            raise ValueError(
                f"WearConfig.clock={self.clock!r}: expected one of {CLOCKS}")

    @property
    def window_write_budget(self) -> int:
        # M writes per BLOCK per window, tracked at superset granularity:
        # budget = 512 * M writes per superset per window (§8).
        return self.blocks_per_superset * self.m_writes


def make_config(n_supersets: int, m_writes: int = 3,
                t_life_years: float = 10.0, endurance: float = 1e8,
                clock: str = "ops", **kw) -> WearConfig:
    """WearConfig with the t_MWW window derived from a lifetime target.

    ``clock="ops"`` (default) keeps the historic CPU-cycle proxy:
    ``t_mww_cycles = t_MWW_seconds * CPU_HZ``.  ``clock="wall"`` expresses
    the window in wall microseconds (``t_MWW_seconds * WALL_HZ``) so
    callers stamping wall time get a true latency-era window."""
    t_mww_s = t_mww_seconds(m_writes, t_life_years * 365.25 * 24 * 3600, endurance)
    hz = CPU_HZ if clock == "ops" else WALL_HZ
    return WearConfig(
        n_supersets=n_supersets, m_writes=m_writes,
        t_mww_cycles=int(t_mww_s * hz), clock=clock, **kw,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WearDyn:
    """Dynamic (traced) wear knobs — the batched simulator stacks one of
    these per config and ``jax.vmap``s over them, so the durability
    parameters (M, counter limits, window length) become data rather than
    compile-time constants.  Field names mirror the ``WearConfig``
    attributes ``record_write``/``rotate_signal``/``wr_signal`` read, so
    either can be passed as ``cfg``; only ``n_supersets`` (an array shape)
    must stay static."""
    window_write_budget: jnp.ndarray   # scalar int32 = blocks/superset * M
    dc_limit: jnp.ndarray              # scalar int32
    wc_limit: jnp.ndarray              # scalar int32
    wr_shift: jnp.ndarray              # scalar int32
    t_mww_cycles: jnp.ndarray          # scalar int32


def dyn_of(cfg: WearConfig) -> WearDyn:
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return WearDyn(
        window_write_budget=i32(cfg.window_write_budget),
        dc_limit=i32(cfg.dc_limit), wc_limit=i32(cfg.wc_limit),
        wr_shift=i32(cfg.wr_shift), t_mww_cycles=i32(cfg.t_mww_cycles),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WearState:
    swt_w: jnp.ndarray          # (S,) int8 — written flag
    swt_d: jnp.ndarray          # (S,) int8 — dirty flag
    write_counter: jnp.ndarray  # scalar int32
    superset_counter: jnp.ndarray
    dirty_counter: jnp.ndarray
    offsets: geometry.RotaryOffsets
    # t_MWW window tracking, per superset.
    window_writes: jnp.ndarray  # (S,) int32 writes in current window
    window_start: jnp.ndarray   # (S,) int64 cycle the window opened
    locked_until: jnp.ndarray   # (S,) int64 cycle until which superset is locked
    total_rotates: jnp.ndarray  # scalar int32
    total_flushed: jnp.ndarray  # scalar int32 — dirty supersets flushed


def init_state(cfg: WearConfig) -> WearState:
    s = cfg.n_supersets
    return WearState(
        swt_w=jnp.zeros((s,), jnp.int8),
        swt_d=jnp.zeros((s,), jnp.int8),
        write_counter=jnp.zeros((), jnp.int32),
        superset_counter=jnp.zeros((), jnp.int32),
        dirty_counter=jnp.zeros((), jnp.int32),
        offsets=geometry.zero_offsets(),
        window_writes=jnp.zeros((s,), jnp.int32),
        window_start=jnp.zeros((s,), jnp.int32),
        locked_until=jnp.zeros((s,), jnp.int32),
        total_rotates=jnp.zeros((), jnp.int32),
        total_flushed=jnp.zeros((), jnp.int32),
    )


def msb_index(x: jnp.ndarray) -> jnp.ndarray:
    """Index of the most-significant non-zero bit; -1 for zero (Fig. 8's
    divider-free ratio detector operates on these)."""
    x32 = x.astype(jnp.uint32)
    clz = jax.lax.clz(x32)
    return jnp.where(x32 == 0, jnp.int32(-1), (31 - clz).astype(jnp.int32))


def wr_signal(state: WearState, cfg: WearConfig) -> jnp.ndarray:
    """WR=1 when msb(write_counter) - msb(superset_counter) >= wr_shift
    (the divider-free 512x ratio detector, Fig. 8)."""
    wmsb = msb_index(state.write_counter)
    smsb = msb_index(state.superset_counter)
    return ((wmsb - smsb) >= cfg.wr_shift) & (state.superset_counter > 0)


def rotate_signal(state: WearState, cfg: WearConfig) -> jnp.ndarray:
    wc = state.write_counter >= cfg.wc_limit
    dc = state.dirty_counter >= cfg.dc_limit
    return wr_signal(state, cfg) | wc | dc


def is_locked(state: WearState, superset: jnp.ndarray, cycle: jnp.ndarray) -> jnp.ndarray:
    return cycle < state.locked_until[superset]


def record_write(state: WearState, cfg: WearConfig, superset: jnp.ndarray,
                 makes_dirty: jnp.ndarray, cycle: jnp.ndarray):
    """Account one XAM write to ``superset`` at ``cycle``.

    Returns (new_state, rotated:bool, flushed_count:int32).
    Handles, in order: t_MWW window rollover, budget accounting + lock,
    SWT/counter updates, rotate detection + offset bump + SWT reset.
    """
    s = superset
    cycle = cycle.astype(jnp.int32)

    # --- t_MWW window (rollover arithmetic shared with the reject-
    # before-write predicate, see _window_now) ----------------------------
    win, expired, w_writes = _window_now(state, cfg, s, cycle)
    w_start = jnp.where(expired, cycle, state.window_start[s])
    w_writes = w_writes + 1
    over = w_writes > cfg.window_write_budget
    locked_until = jnp.where(over, w_start + win, state.locked_until[s])

    window_writes = state.window_writes.at[s].set(w_writes)
    window_start = state.window_start.at[s].set(w_start)
    locked = state.locked_until.at[s].set(locked_until)

    # --- SWT + counters (Fig. 8) ------------------------------------------
    first_write = state.swt_w[s] == 0
    superset_counter = state.superset_counter + jnp.where(first_write, 1, 0).astype(jnp.int32)
    swt_w = state.swt_w.at[s].set(1)
    newly_dirty = (state.swt_d[s] == 0) & makes_dirty
    dirty_counter = state.dirty_counter + jnp.where(newly_dirty, 1, 0).astype(jnp.int32)
    swt_d = state.swt_d.at[s].max(makes_dirty.astype(jnp.int8))
    write_counter = state.write_counter + 1

    mid = WearState(
        swt_w=swt_w, swt_d=swt_d,
        write_counter=write_counter, superset_counter=superset_counter,
        dirty_counter=dirty_counter, offsets=state.offsets,
        window_writes=window_writes, window_start=window_start,
        locked_until=locked,
        total_rotates=state.total_rotates, total_flushed=state.total_flushed,
    )

    rot = rotate_signal(mid, cfg)
    flushed = jnp.where(rot, jnp.sum(swt_d.astype(jnp.int32)), 0)

    def do_rotate(st: WearState) -> WearState:
        return WearState(
            swt_w=jnp.zeros_like(st.swt_w),
            swt_d=jnp.zeros_like(st.swt_d),
            write_counter=jnp.zeros_like(st.write_counter),
            superset_counter=jnp.zeros_like(st.superset_counter),
            dirty_counter=jnp.zeros_like(st.dirty_counter),
            offsets=geometry.apply_rotate(st.offsets),
            window_writes=st.window_writes,
            window_start=st.window_start,
            locked_until=st.locked_until,
            total_rotates=st.total_rotates + 1,
            total_flushed=st.total_flushed + flushed,
        )

    new_state = jax.lax.cond(rot, do_rotate, lambda st: st, mid)
    return new_state, rot, flushed


# ---------------------------------------------------------------------------
# Batched device ops.  The serving path (serve/kv_index.py), the hashtable
# app, and the differential tests all consume the SAME per-write semantics as
# the simulator — there is exactly one implementation of §8, this module —
# but amortize dispatch by applying a whole write trace per device call:
# ``record_writes`` is a ``lax.scan`` over ``record_write``, so it is
# step-for-step identical to the host loop while costing one dispatch.
# ---------------------------------------------------------------------------

def _window_now(state: WearState, cfg, superset, cycle):
    """THE t_MWW window-rollover arithmetic (one implementation, shared by
    ``record_write`` and ``window_would_exceed``): returns
    ``(win, expired, writes_now)`` for ``superset`` at ``cycle``.

    Clock-agnostic by construction: only int32 DIFFERENCES of ``cycle``
    against stored stamps are compared, so the same predicate serves the
    op-counter proxy (``clock="ops"``) and wall-microsecond stamps
    (``clock="wall"``) — the caller just has to stamp consistently in
    one domain (``WearConfig.clock`` records which)."""
    win = jnp.maximum(jnp.asarray(cfg.t_mww_cycles, jnp.int32), 1)
    expired = (cycle - state.window_start[superset]) >= win
    writes_now = jnp.where(expired, 0, state.window_writes[superset])
    return win, expired, writes_now


def window_would_exceed(state: WearState, cfg, superset: jnp.ndarray,
                        cycle: jnp.ndarray) -> jnp.ndarray:
    """Reject-before-write t_MWW predicate (§6.2 lifetime throttle).

    Parameters
    ----------
    state : WearState
        Current wear state (host or device resident).
    cfg : WearConfig | WearDyn
        Source of ``window_write_budget`` / ``t_mww_cycles`` — static
        config and traced dynamic knobs are interchangeable here.
    superset : jnp.ndarray, int32 (scalar or (N,))
        Superset id(s) the prospective write targets.
    cycle : jnp.ndarray, int32
        Current cycle in the config's clock domain (serving stamps its
        op counter under ``clock="ops"``, wall microseconds under
        ``clock="wall"``).

    Returns
    -------
    jnp.ndarray, bool (same shape as ``superset``)
        True when ONE more write at ``cycle`` would blow the t_MWW window
        budget.  Admission controllers (cache-mode serving) consult this
        BEFORE spending the XAM write, unlike the simulator's
        lock-after-overflow accounting in :func:`record_write` — both use
        the same ``_window_now`` rollover arithmetic.
    """
    cycle = jnp.asarray(cycle, jnp.int32)
    _, _, writes_now = _window_now(state, cfg, superset, cycle)
    return (writes_now + 1) > cfg.window_write_budget


def record_writes(state: WearState, cfg, supersets, makes_dirty, cycles,
                  active=None):
    """Batched :func:`record_write`: apply a trace of writes in order.

    Parameters
    ----------
    state : WearState
        State the trace starts from.
    cfg : WearConfig | WearDyn
        Durability knobs (static or traced).
    supersets : (B,) int32
        Target superset per write, in trace order.
    makes_dirty : (B,) bool
        Whether each write dirties its superset (drives the DC counter).
    cycles : (B,) int32
        Cycle stamp per write (monotone within the trace).
    active : (B,) bool, optional
        Masks padding lanes (pow2-bucketed callers) — an inactive lane is
        a no-op on state AND outputs.

    Returns
    -------
    (state, rotated, flushed)
        New state, per-step rotate flags ``(B,) bool`` and flushed-superset
        counts ``(B,) int32``.  The per-step outputs match a Python loop
        over :func:`record_write` exactly (pinned by tests/test_wear.py's
        differential trace tests).
    """
    supersets = jnp.asarray(supersets, jnp.int32)
    makes_dirty = jnp.asarray(makes_dirty, bool)
    cycles = jnp.asarray(cycles, jnp.int32)
    act = (jnp.ones(supersets.shape, bool) if active is None
           else jnp.asarray(active, bool))

    def step(st, x):
        s, d, c, a = x
        st2, rot, fl = record_write(st, cfg, s, d, c)
        st = jax.tree.map(lambda o, n: jnp.where(a, n, o), st, st2)
        return st, (rot & a, jnp.where(a, fl, 0))

    state, (rots, fls) = jax.lax.scan(
        step, state, (supersets, makes_dirty, cycles, act))
    return state, rots, fls


#: Device entry point for :func:`record_writes`: the state argument is
#: DONATED (the caller's reference is invalid after the call — rebind to
#: the returned state), so a long-lived serving/app loop costs one device
#: dispatch and zero state copies per write batch.
record_writes_device = functools.partial(
    jax.jit, donate_argnums=(0,))(record_writes)


def record_write_rows(state: WearState, cfg, supersets, cycles, active,
                      makes_dirty=None) -> WearState:
    """Vectorized :func:`record_write` over DISTINCT supersets — one fully
    parallel row update instead of a scan.

    Bit-identical to folding :func:`record_write` over the lanes in any
    order, PROVIDED two contract conditions hold (the caller's to keep):

    * the active supersets are pairwise distinct — every per-superset row
      (window fields, SWT flags) is touched by at most one lane, so the
      row scatters commute and the scalar counters become order-free sums;
    * the rotate signals are disabled (``wr_shift >= 32`` and huge
      WC/DC limits, the serving index's configuration) — ``record_write``'s
      rotate branch is then the identity, so offsets / ``total_rotates`` /
      ``total_flushed`` are invariants and are passed through untouched.

    The single-dispatch admission path (serve/kv_index.py) calls this once
    per admission round; its round grid holds distinct sets per round by
    construction.  Inactive lanes are full no-ops (gathers are clipped,
    scatters dropped via an out-of-bounds sentinel index).

    Parameters
    ----------
    state : WearState
    cfg : WearConfig | WearDyn
        Durability knobs (static or traced).
    supersets : (K,) int32
        Target superset per lane; active lanes must be pairwise distinct.
    cycles : (K,) int32
        Cycle stamp per lane.
    active : (K,) bool
        Lane mask; an inactive lane changes nothing.
    makes_dirty : (K,) bool, optional
        Defaults to all-dirty (the serving install path).

    Returns
    -------
    WearState

    Examples
    --------
    >>> import numpy as np
    >>> cfg = WearConfig(n_supersets=4, t_mww_cycles=100,
    ...                  blocks_per_superset=2, wr_shift=32)
    >>> st = record_write_rows(
    ...     init_state(cfg), cfg, np.array([0, 2, 1], np.int32),
    ...     np.array([5, 6, 7], np.int32), np.array([True, True, False]))
    >>> np.asarray(st.window_writes).tolist(), int(st.write_counter)
    ([1, 0, 1, 0], 2)
    """
    s = jnp.asarray(supersets, jnp.int32)
    cycle = jnp.asarray(cycles, jnp.int32)
    act = jnp.asarray(active, bool)
    dirty = (jnp.ones(s.shape, bool) if makes_dirty is None
             else jnp.asarray(makes_dirty, bool))
    n = state.swt_w.shape[0]
    sc = jnp.clip(s, 0, n - 1)          # gather-safe row index
    ii = jnp.where(act, sc, n)          # scatter index: OOB drop when inactive

    # t_MWW window accounting — the same _window_now rollover arithmetic,
    # one lane per (distinct) superset row.
    win, expired, w_writes = _window_now(state, cfg, sc, cycle)
    w_start = jnp.where(expired, cycle, state.window_start[sc])
    w_writes = w_writes + 1
    over = w_writes > cfg.window_write_budget
    locked_until = jnp.where(over, w_start + win, state.locked_until[sc])

    window_writes = state.window_writes.at[ii].set(w_writes, mode="drop")
    window_start = state.window_start.at[ii].set(w_start, mode="drop")
    locked = state.locked_until.at[ii].set(locked_until, mode="drop")

    # SWT + counters: per-row flags scatter (disjoint rows), scalar
    # counters as sums over the lanes (order-free because each lane's
    # first_write/newly_dirty depends only on its own pre-batch row).
    first_write = (state.swt_w[sc] == 0) & act
    superset_counter = (state.superset_counter
                        + jnp.sum(first_write.astype(jnp.int32)))
    swt_w = state.swt_w.at[ii].set(jnp.int8(1), mode="drop")
    newly_dirty = (state.swt_d[sc] == 0) & dirty & act
    dirty_counter = (state.dirty_counter
                     + jnp.sum(newly_dirty.astype(jnp.int32)))
    swt_d = state.swt_d.at[ii].max(dirty.astype(jnp.int8), mode="drop")
    write_counter = state.write_counter + jnp.sum(act.astype(jnp.int32))

    return WearState(
        swt_w=swt_w, swt_d=swt_d,
        write_counter=write_counter, superset_counter=superset_counter,
        dirty_counter=dirty_counter, offsets=state.offsets,
        window_writes=window_writes, window_start=window_start,
        locked_until=locked,
        total_rotates=state.total_rotates, total_flushed=state.total_flushed,
    )


#: Serving clock re-base threshold.  The cycle domain is int32 (JAX's
#: default integer width); a long-lived op-counter clock must be folded
#: back before it wraps.  Every window comparison is difference-based, so
#: shifting the clock AND every stored timestamp by the same delta is an
#: exact no-op semantically.
CLOCK_REBASE_AT = 1 << 30


def maybe_rebase(state: WearState, op_counter: int):
    """The serving wrap policy in one place: fold ``op_counter`` (and the
    state's timestamps, via :func:`rebase_clock`) once it reaches
    CLOCK_REBASE_AT.  Returns ``(state, op_counter)``."""
    if op_counter >= CLOCK_REBASE_AT:
        state = rebase_clock(state, CLOCK_REBASE_AT)
        op_counter -= CLOCK_REBASE_AT
    return state, op_counter


def rebase_clock(state: WearState, delta) -> WearState:
    """Shift all stored timestamps down by ``delta`` (callers shift their
    op counter in lockstep).  Timestamps are floored at -CLOCK_REBASE_AT so
    repeated rebases cannot underflow int32: an entry at the floor is, and
    behaves as, long-expired/unlocked (exact as long as window lengths are
    <= CLOCK_REBASE_AT, which the int32 ``t_mww_cycles`` domain and callers
    guarantee)."""
    d = jnp.asarray(delta, jnp.int32)
    floor = jnp.int32(-CLOCK_REBASE_AT)
    return dataclasses.replace(
        state,
        window_start=jnp.maximum(state.window_start - d, floor),
        locked_until=jnp.maximum(state.locked_until - d, floor),
    )


# ---------------------------------------------------------------------------
# L3-eviction write-mitigation filter (§8 "Mitigating Writes").
# D (dirty) and R (read-since-install) flags decide the fate of an evicted
# block:  D&R -> install/update in Monarch;  D&!R -> forward to main memory;
# !D&R -> install as read-only;  !D&!R -> drop.
# ---------------------------------------------------------------------------

def install_decision(dirty: jnp.ndarray, read: jnp.ndarray):
    """Fate of an L3-evicted block from its D (dirty) / R (read) flags.

    Returns ``(install_in_monarch, forward_to_dram)`` — read blocks
    install, dirty-never-read blocks are forwarded, clean-never-read
    blocks are dropped:

    >>> import numpy as np
    >>> inst, fwd = install_decision(np.array([1, 1, 0, 0]),
    ...                              np.array([1, 0, 1, 0]))
    >>> np.asarray(inst).tolist(), np.asarray(fwd).tolist()
    ([True, False, True, False], [False, True, False, False])
    """
    dirty = dirty.astype(bool)
    read = read.astype(bool)
    install = read  # D&R and !D&R install
    forward = dirty & ~read  # D&!R forwarded to DRAM
    return install, forward
