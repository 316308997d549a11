"""Chip smoke: the serving path and the memory's kernels, once, on a TPU.

    python3 chip_smoke.py                # one chip: serving + kernel phases
    python3 chip_smoke.py --four-chips   # four chips: the set-sharded index

Serving phase (one chip).  Boots the real stack through
``repro.launch.httpd`` — yi-9b at its published widths, the first 24 of
its 48 layers (one chip's share of a two-stage pipeline), random weights
from a seed — on an ephemeral loopback port, and POSTs six batch-2
requests of 96 tokens that share a 48-token prefix to ``/v1/generate``.
Every request must answer 200 with a (2, 8) token array; every request
after the first must hit the prefix cache and resume from KV slabs.  One
resumed prompt's last-position logits must match a full prefill of the
same prompt within ``LOGIT_TOL``.  The drain must end with no router
error and the slab store in lockstep with the index.

Kernel phase (one chip).  One call each of the flat XAM search, the
device hopscotch lookup over a 1M-slot table and the string match over
32 MiB of seeded text, each compared exactly with its ``ref.py`` (and
the table's answers with what was inserted).  Every Pallas kernel on the
path must lower to a TPU custom call: none may run interpreted.

Four-chip phase (``--four-chips``, and nothing else).  The same request
sequence through ``build_frontend`` with ``--n-shards 4`` (the index's
sets split over four chips: one ``shard_map`` lookup, stacked admission,
rotation as a ``ppermute`` boundary exchange) and with ``--n-shards 1``,
one rotation forced between requests; both must agree exactly.

The last stdout line is ``{"ok": true, "device": {...}}``.  Without a
TPU, or on any failed check, the script exits non-zero before printing
it.  Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

SEED = 0
MODEL_ARGS = ["--arch", "yi-9b", "--n-layers", "24"]
# The four-chip phase compares index behaviour, not the model: two layers
# at published widths keep both sessions' replicated weights on each chip.
FOUR_CHIP_MODEL_ARGS = ["--arch", "yi-9b", "--n-layers", "2"]
N_REQUESTS, BATCH, PROMPT, PREFIX, DECODE = 6, 2, 96, 48, 8
ROTATE_AFTER = 3          # four-chip phase: rotate after this many requests
# Resumed vs full prefill are both bf16 on the MXU, but the resumed one
# multiplies only the 48-token suffix, so XLA may tile it and order the
# bf16 accumulation differently, and a rounding flipped in one layer is
# carried through every later one.  Bound: four bf16 ulps (2**-5) of the
# largest logit.
LOGIT_TOL = 2.0 ** -5
# Kernel-phase sizes: a flat search over 64K columns of 64-bit keys, a
# 2**20-slot hopscotch table (window 32) and 32 MiB of text.
XAM_Q, XAM_R, XAM_C = 128, 64, 1 << 16
HOP_LOG2_SLOTS, HOP_WINDOW, HOP_INSERTS = 20, 32, 4096
TEXT_BYTES, PATTERN_LEN = 32 << 20, 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def assert_compiled(jitted, *args, **kw) -> None:
    """The kernel lowers to a Mosaic TPU custom call (not interpret)."""
    txt = jitted.lower(*args, **kw).as_text()
    check("tpu_custom_call" in txt,
          f"{getattr(jitted, '__name__', jitted)} would run interpreted")


def peak_gb() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


# ---------------------------------------------------------------------------
# Serving through the HTTP edge.
# ---------------------------------------------------------------------------

def make_requests(vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(1, vocab, PREFIX)
    return [np.concatenate(
        [np.tile(prefix, (BATCH, 1)),
         rng.integers(1, vocab, (BATCH, PROMPT - PREFIX))],
        axis=1).astype(np.int32) for _ in range(N_REQUESTS)]


def post(port: int, toks: np.ndarray) -> dict:
    # No proxy: the request must stay on loopback whatever the environment.
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"tokens": toks.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with opener.open(req, timeout=600) as r:
        check(r.status == 200, f"HTTP {r.status}")
        return json.loads(r.read())


def serve_session(model_args, *, n_shards: int, wear_clock: str = "wall",
                  rotate_after: int | None = None,
                  check_logits: bool = False) -> dict:
    """Boot the stack, serve the request sequence over HTTP, drain.

    Returns per-request answers plus the index state after the drain."""
    import jax
    from repro.kernels.xam_search.kernel import xam_search_multiset_pallas
    from repro.launch.httpd import build_frontend, build_parser
    from repro.launch.serve import model_config

    args = build_parser().parse_args(model_args + [
        "--port", "0", "--n-shards", str(n_shards), "--prompt-len",
        str(PROMPT), "--decode-tokens", str(DECODE),
        "--admit-after-reads", "0", "--wear-clock", wear_clock])
    t0 = time.perf_counter()
    frontend, admit_q = build_frontend(args)
    boot_s = time.perf_counter() - t0
    idx = admit_q.index
    check(idx.n_parts == min(n_shards, len(jax.devices())),
          f"index holds {idx.n_parts} partitions for --n-shards {n_shards}")
    frontend.start()
    port = frontend.address[1]
    vocab = model_config(args).vocab_size
    answers, times = [], []
    for i, toks in enumerate(make_requests(vocab)):
        if rotate_after is not None and i == rotate_after:
            admit_q.rotate()
        t = time.perf_counter()
        doc = post(port, toks)
        times.append(time.perf_counter() - t)
        out = np.asarray(doc["tokens"])
        check(out.shape == (BATCH, DECODE), f"request {i}: tokens shape "
              f"{out.shape}, want {(BATCH, DECODE)}")
        check(((out >= 0) & (out < vocab)).all(),
              f"request {i}: token ids outside the vocabulary")
        if i > 0:
            check(doc["hit_chunks"] > 0 and doc["resumed_chunks"] > 0,
                  f"request {i}: hit_chunks={doc['hit_chunks']} "
                  f"resumed_chunks={doc['resumed_chunks']}")
        answers.append({"tokens": out.tolist(), "chunks": doc["chunks"],
                        "hit_chunks": doc["hit_chunks"],
                        "resumed_chunks": doc["resumed_chunks"]})

    # The index lookup the router ran must be the compiled kernel.
    bq = 16
    assert_compiled(
        xam_search_multiset_pallas,
        np.zeros((bq, idx.cfg.key_bits), np.int8),
        np.zeros((bq, idx.cfg.key_bits), np.int8),
        idx.bits, idx.valid, np.zeros(1, np.int32), np.ones(1, np.int32),
        block_q=bq)

    logit_err = None
    if check_logits:
        toks = make_requests(vocab)[-1]
        prefill = frontend.router.prefill_fn
        resumed = prefill(toks, admit_q.lookup(toks))
        check(resumed.resumed_chunks > 0, "logit check prompt did not resume")
        full = prefill(toks, None)
        lr = np.asarray(resumed.state["logits"], np.float32)
        lf = np.asarray(full.state["logits"], np.float32)
        scale = float(np.abs(lf).max())
        logit_err = float(np.abs(lr - lf).max())
        check(np.isfinite(lr).all() and np.isfinite(lf).all(),
              "non-finite logits")
        check(logit_err <= LOGIT_TOL * scale,
              f"resumed vs full prefill logits differ by {logit_err:.4g} "
              f"> {LOGIT_TOL:g} x max |logit| {scale:.4g}")
        top2 = np.sort(lf, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 2 * LOGIT_TOL * scale
        check((lr.argmax(-1) == lf.argmax(-1))[clear].all(),
              "greedy token differs where the top-2 margin is clear")
        log(f"resumed vs full prefill: max |dlogit| {logit_err:.6g} "
            f"(bound {LOGIT_TOL * scale:.6g} = {LOGIT_TOL:g} x max |logit| "
            f"{scale:.6g}); greedy tokens equal on {int(clear.sum())}/"
            f"{len(clear)} rows with a clear margin")

    frontend.shutdown()
    rstats = frontend.router.stats
    lockstep = idx.slab_lockstep_report()
    state = {
        "answers": answers,
        "admissions": idx.stats.admissions,
        "rotations": idx.stats.rotations,
        "planes": [np.asarray(p).tolist() for p in
                   (idx.bits, idx.valid, idx.fp_of, idx.read_after)],
        "wear": idx.wear_report(),
    }
    admit_q.close()
    check(rstats.errors == 0, f"{rstats.errors} router errors")
    check(rstats.completed == N_REQUESTS,
          f"{rstats.completed}/{N_REQUESTS} requests completed")
    check(not lockstep["missing_slabs"] and not lockstep["orphan_slabs"],
          f"slab store out of lockstep: {lockstep}")
    hits = sum(a["hit_chunks"] for a in answers)
    resumed = sum(a["resumed_chunks"] for a in answers)
    log(f"n_shards={n_shards}: boot (init + warm compile) {boot_s:.3f} s; "
        f"request 0 (compiles the full prefill + decode) {times[0]:.3f} s, "
        f"request 1 (compiles the resumed prefill) {times[1]:.3f} s, "
        f"requests 2-{N_REQUESTS - 1} mean "
        f"{np.mean(times[2:]):.3f} s; {rstats.completed} answered, "
        f"{hits} hit chunks, {resumed} resumed chunks, "
        f"{idx.stats.admissions} admissions, {idx.stats.rotations} "
        f"rotations; peak {peak_gb():.3f} GB in use")
    return state


# ---------------------------------------------------------------------------
# The memory's own kernels at deployment sizes.
# ---------------------------------------------------------------------------

def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.apps.hashtable import HopscotchTable
    from repro.kernels.hopscotch import ops as hop_ops
    from repro.kernels.hopscotch.kernel import hopscotch_lookup_pallas
    from repro.kernels.hopscotch.ref import hopscotch_lookup_ref
    from repro.kernels.string_match import ops as sm_ops
    from repro.kernels.string_match.kernel import string_match_pallas
    from repro.kernels.string_match.ref import string_match_ref
    from repro.kernels.xam_search import ops as xam_ops
    from repro.kernels.xam_search.kernel import xam_search_pallas
    from repro.kernels.xam_search.ref import xam_search_ref

    rng = np.random.default_rng(SEED)

    # Flat XAM search: random 64-bit keys with a quarter of the bits
    # masked out; 64 columns planted equal to a key so some must match.
    keys = rng.integers(0, 2, (XAM_Q, XAM_R)).astype(np.int8)
    data = rng.integers(0, 2, (XAM_R, XAM_C)).astype(np.int8)
    masks = (rng.random((XAM_Q, XAM_R)) >= 0.25).astype(np.int8)
    cols = rng.choice(XAM_C, 64, replace=False)
    data[:, cols] = keys[rng.integers(0, XAM_Q, 64)].T
    t = time.perf_counter()
    got = np.asarray(xam_ops.xam_search(keys, data, masks))
    dt = time.perf_counter() - t
    want = np.asarray(jax.jit(xam_search_ref)(keys, data, masks))
    check(np.array_equal(got, want), "xam_search differs from ref")
    check(got.sum() >= 64, "planted XAM matches not found")
    assert_compiled(xam_search_pallas, jnp.asarray(keys), jnp.asarray(data),
                    jnp.asarray(masks))
    log(f"xam_search {XAM_Q}x{XAM_R} keys vs {XAM_C} columns == ref "
        f"({int(got.sum())} matches; first call incl. compile {dt:.3f} s)")

    # Device hopscotch table: seeded inserts, then half resident and half
    # absent keys through the fused window lookup.
    table = HopscotchTable(HOP_LOG2_SLOTS, window=HOP_WINDOW,
                           backend="device")
    ins = np.unique(rng.integers(1, 2 ** 63, 2 * HOP_INSERTS,
                                 dtype=np.uint64))[:HOP_INSERTS]
    vals = rng.integers(1, 2 ** 63, ins.size, dtype=np.uint64)
    t = time.perf_counter()
    for k, v in zip(ins, vals):
        table.insert(int(k), int(v))
    t_ins = time.perf_counter() - t
    absent = np.setdiff1d(
        rng.integers(1, 2 ** 63, HOP_INSERTS, dtype=np.uint64), ins)
    probe = np.concatenate([ins[: HOP_INSERTS // 2], absent])
    t = time.perf_counter()
    got_v, got_hit = table.lookup_monarch(probe)
    dt = time.perf_counter() - t
    n_in = HOP_INSERTS // 2
    check(got_hit[:n_in].all() and not got_hit[n_in:].any(),
          "hopscotch hits differ from the inserted key set")
    check(np.array_equal(got_v[:n_in], vals[:n_in]),
          "hopscotch values differ from the inserted values")
    # Kernel vs ref.py on the table's own device planes.
    t_lo, t_hi = table._table_planes()
    homes = table.home(probe).astype(np.int32)
    q_lo = (probe & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    q_hi = (probe >> np.uint64(32)).astype(np.uint32)
    off = np.asarray(hop_ops.hopscotch_lookup(
        t_lo, t_hi, homes, q_lo, q_hi, window=HOP_WINDOW))
    ref = np.asarray(hopscotch_lookup_ref(
        t_lo, t_hi, jnp.asarray(homes), jnp.asarray(q_lo),
        jnp.asarray(q_hi), HOP_WINDOW))
    check(np.array_equal(off, ref), "hopscotch lookup differs from ref")
    qp = 8 * (-(-probe.size // 8))
    assert_compiled(hopscotch_lookup_pallas, t_lo, t_hi,
                    jnp.zeros(qp, jnp.int32), jnp.zeros(qp, jnp.uint32),
                    jnp.zeros(qp, jnp.uint32), window=HOP_WINDOW)
    log(f"hopscotch {1 << HOP_LOG2_SLOTS} slots, {ins.size} inserts "
        f"({t_ins:.3f} s), {probe.size} lookups == inserted set and ref "
        f"(lookup incl. compile {dt:.3f} s, load {table.load:.4f})")

    # String match over seeded text from an 8-letter alphabet.
    text = rng.integers(97, 105, TEXT_BYTES).astype(np.uint8)
    start = int(rng.integers(0, TEXT_BYTES - PATTERN_LEN))
    pattern = text[start:start + PATTERN_LEN].copy()
    t = time.perf_counter()
    got = np.asarray(sm_ops.string_match(text, pattern))
    dt = time.perf_counter() - t
    want = np.asarray(jax.jit(string_match_ref)(text, pattern))
    check(np.array_equal(got, want), "string_match differs from ref")
    check(got[start] == 1, "planted pattern not found")
    assert_compiled(string_match_pallas, jnp.asarray(text),
                    jnp.asarray(pattern), pattern_len=PATTERN_LEN)
    log(f"string_match {TEXT_BYTES >> 20} MiB text, {PATTERN_LEN}-byte "
        f"pattern == ref ({int(got.sum())} matches; incl. compile "
        f"{dt:.3f} s)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the set-sharded index phase on 4 chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)
    from repro.kernels.common import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    check(resolve_interpret(None) is False,
          "Pallas kernels would run interpreted")
    log(f"devices: {len(devs)} x {devs[0].device_kind}")

    if args.four_chips:
        runs = {n: serve_session(FOUR_CHIP_MODEL_ARGS, n_shards=n,
                                 wear_clock="ops",
                                 rotate_after=ROTATE_AFTER)
                for n in (4, 1)}
        for key in ("answers", "admissions", "rotations", "planes", "wear"):
            check(runs[4][key] == runs[1][key],
                  f"--n-shards 4 and --n-shards 1 differ in {key}")
        a = runs[4]["answers"]
        log("n_shards 4 == n_shards 1 exactly: per-request hit chunks "
            f"{[x['hit_chunks'] for x in a]}, resumed chunks "
            f"{[x['resumed_chunks'] for x in a]}, decoded tokens, "
            f"{runs[4]['admissions']} installs, "
            f"{runs[4]['rotations']} rotation, planes and wear report "
            f"(installs/set max {runs[4]['wear']['installs_per_set_max']})")
    else:
        serve_session(MODEL_ARGS, n_shards=1, check_logits=True)
        kernel_phase()
    log(f"peak bytes in use {peak_gb():.3f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
