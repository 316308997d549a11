"""Production serving launcher: batched prefill + decode with the
MonarchKVIndex prefix cache.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
        --requests 8 --decode-tokens 8 [--mesh host|single|multi]
    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --n-layers 24

(``--reduced`` shrinks every width for a CPU run; ``--n-layers`` keeps
the published widths and cuts depth to one chip's share.)

The request loop is the same flow examples/serve_prefix_cache.py
demonstrates; this launcher adds mesh placement (params TP/FSDP-sharded,
cache sharded per ``cache_specs`` — ``--seq-shard-kv`` enables the §Perf
split-KV layout) and batch scheduling over a request queue.  The loop
itself lives in :func:`run_request_loop` — one implementation shared by
this launcher (closed-loop: the next batch starts when the previous
finished) and by ``benchmarks/serve_bench.py`` (open-loop: scheduled
Poisson/replayed-trace arrivals, latency charged from the SCHEDULED
arrival so backlog shows up as queueing delay instead of being
coordinated-omission'd away).

Index scaling knobs (see docs/SERVING.md for the full operator guide):
``--n-shards`` splits the Monarch index's CAM sets across the
``("sets",)`` device mesh — lookups run as ONE ``shard_map`` dispatch
over the stacked layout and rotation stays device-resident (``ppermute``
boundary exchange); on a single-device host every shard co-locates and
the index collapses to the unsharded single-launch path.  Admissions run
behind an async ``AdmitQueue`` by default — installs overlap the decode
loop — with ``--sync-admit`` restoring the inline path.  Front-end SLO
knobs: ``--wear-clock wall`` makes the §6.2 admission window a
wall-clock time budget instead of the op-counter proxy;
``--max-pending`` bounds the admission queue with ``--admit-policy``
``block`` / ``shed`` / ``defer`` back-pressure.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import configs
from repro.dist import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer
from repro.serve import step as serve_step
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import (CHUNK_TOKENS, KVIndexConfig, KVSlabStore,
                                  MonarchKVIndex)
from repro.serve.resume import PrefillResult, PrefixResumeEngine


@dataclasses.dataclass
class RequestRecord:
    """Per-request front-end accounting from :func:`run_request_loop`.

    ``latency_s`` is measured from the SCHEDULED arrival when the loop
    runs open-loop (``arrivals_s`` given): a request that arrived while
    the loop was still busy is charged its backlog wait, which is what
    makes open-loop p99 honest under overload.  Closed-loop, arrival ==
    start and latency is pure service time."""
    arrival_s: float            # scheduled (open-loop) or actual start
    start_s: float              # when the loop began serving it
    done_s: float               # when service + submit finished
    latency_s: float            # done_s - arrival_s
    chunks: int                 # whole CHUNK_TOKENS chunks looked up
    hit_chunks: int             # of which already cached
    admitted: bool              # admission submit accepted
    retried: bool               # defer policy: submit retried after decode
    dropped: bool               # retry rejected too — admission forgone
    resumed_chunks: int = 0     # chunks restored from KV slabs (resume path)
    decoded: np.ndarray | None = None   # decode_fn's (B, T) greedy tokens
    # time.monotonic() when the first decoded token reached the host
    # (resume engine's decode; None on other paths)
    first_token_at: float | None = None


def run_request_loop(admit_q: AdmitQueue, requests, *, prefill_fn,
                     decode_fn=None, arrivals_s=None, now_fn=time.monotonic,
                     sleep_fn=time.sleep, retry_wait_s=0.05, on_batch=None):
    """THE serving request loop: lookup -> prefill -> submit -> decode.

    Parameters
    ----------
    admit_q : AdmitQueue
        Front end over the MonarchKVIndex; every index access goes
        through it (read-your-writes lookups, bounded-queue admission).
    requests : sequence of np.ndarray
        Token batches, one ``(B, S)`` int array per request batch.
    prefill_fn : callable
        ``prefill_fn(tokens, hits) -> state``: compute the batch's KV
        (the launcher's jitted prefill; the bench's service proxy).
        Called BEFORE the admission submit — chunks are offered as soon
        as their KV exists, the PR-4 submit-after-prefill hook.
    decode_fn : callable, optional
        ``decode_fn(tokens, state) -> decoded | None``: the decode loop,
        run after the submit so the admission worker overlaps it.  Its
        return value (the ``(B, decode_tokens)`` greedy token array, or
        ``None`` for decode-less stand-ins) is surfaced on the record as
        ``RequestRecord.decoded`` — the loop never discards output.
    arrivals_s : sequence of float, optional
        OPEN-LOOP arrival offsets (seconds from loop start), one per
        request, nondecreasing.  The loop sleeps until each scheduled
        arrival; when it is running behind, the request is served
        immediately but its latency still counts from the schedule.
        ``None`` = closed loop (next batch starts when the previous
        finished).
    now_fn, sleep_fn : callables
        Clock/sleep injection for tests.
    retry_wait_s : float
        Bounded drain-wait before the ONE defer retry: when the first
        submit is rejected (``policy="defer"``), the loop polls
        ``admit_q.pending()`` via ``sleep_fn`` for at most this long
        before retrying.  Without it, a decode-less caller (the bench's
        service-proxy path) retries immediately into the still-full
        queue and over-counts ``dropped``.  ``0`` restores the
        immediate retry.
    on_batch : callable, optional
        ``on_batch(i, tokens, hits, record)`` after each batch (the
        launcher prints its per-batch report here).

    Returns
    -------
    list[RequestRecord]

    Notes
    -----
    Back-pressure: ``admit_q.submit_tokens`` may reject under
    ``policy="defer"`` — the loop retries ONCE after the decode (the
    queue usually drained meanwhile); a rejected retry forgoes the
    admission (``dropped=True``) rather than stalling the serving path.
    ``policy="block"``/``"shed"`` never reject, so those records always
    carry ``admitted=True``.
    """
    t0 = now_fn()
    records: list[RequestRecord] = []
    for i, toks in enumerate(requests):
        if arrivals_s is not None:
            arrival = float(arrivals_s[i])
            wait = arrival - (now_fn() - t0)
            if wait > 0:
                sleep_fn(wait)
        start = now_fn() - t0
        if arrivals_s is None:
            arrival = start
        hits = admit_q.lookup(toks)
        state = prefill_fn(toks, hits)
        # Resume-aware prefills return a PrefillResult: its freshly
        # computed KV slabs are staged WITH the submit, so the async
        # admission commits slab and fingerprint together (lockstep).
        slabs = state.slabs if isinstance(state, PrefillResult) else None
        resumed = state.resumed_chunks if isinstance(state, PrefillResult) else 0
        # Only resume-aware prefills produce slabs; plain queues (and
        # stand-ins) keep the slab-less submit_tokens(tokens) signature.
        submit = (lambda: admit_q.submit_tokens(toks, slabs=slabs)) \
            if slabs is not None else (lambda: admit_q.submit_tokens(toks))
        accepted = submit()
        decoded = decode_fn(toks, state) if decode_fn is not None else None
        retried = dropped = False
        if not accepted:               # defer: retry once after decode
            retried = True
            # Bounded drain-wait before the single retry: give the
            # admission worker a window to drain below the bound (a
            # decode above usually provided one; a decode-less caller
            # would otherwise race the still-full queue).
            pending_fn = getattr(admit_q, "pending", None)
            if pending_fn is not None and retry_wait_s > 0:
                deadline = now_fn() + retry_wait_s
                while pending_fn() > 0 and now_fn() < deadline:
                    sleep_fn(retry_wait_s / 16)
            accepted = submit()
            dropped = not accepted
            if dropped and slabs:      # forgone admission: staged slabs
                store = admit_q.index.slab_store      # are garbage
                for fp in slabs:
                    store.discard(fp)
        done = now_fn() - t0
        rec = RequestRecord(
            arrival_s=arrival, start_s=start, done_s=done,
            latency_s=done - arrival,
            chunks=int(hits.size), hit_chunks=int(hits.sum()),
            admitted=bool(accepted), retried=retried, dropped=dropped,
            resumed_chunks=resumed, decoded=decoded,
            first_token_at=(state.state.get("first_token_at")
                            if isinstance(state, PrefillResult) else None))
        records.append(rec)
        if on_batch is not None:
            on_batch(i, toks, hits, rec)
    return records


def add_model_args(ap: argparse.ArgumentParser) -> None:
    """``--arch`` / ``--reduced`` / ``--n-layers``: the model selection
    shared by this launcher and the HTTP edge (``launch/httpd.py``)."""
    ap.add_argument("--arch", default="yi-9b", choices=sorted(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU smoke-test variant: every width shrunk "
                         "(ArchConfig.reduced)")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="depth cut that keeps every published width: "
                         "serve the first N layers (24 of yi-9b's 48 is "
                         "one chip's share of a two-stage pipeline)")


def model_config(args):
    """The served ``ArchConfig`` for :func:`add_model_args` flags."""
    cfg = configs.get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.n_layers is not None:
        if not 1 <= args.n_layers <= cfg.n_layers:
            raise SystemExit(f"--n-layers {args.n_layers}: {cfg.name} has "
                             f"{cfg.n_layers} layers")
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode service")
    return cfg


def init_placed_params(cfg, mesh):
    """Random weights (seed 0) made directly in their serving layout:
    ONE jitted init whose ``out_shardings`` are the mesh placement, so
    neither a host copy, a second device copy nor a whole-stack float32
    draw is ever resident (yi-9b at published widths fills most of a
    chip)."""
    init = functools.partial(transformer.init_params, cfg=cfg)
    key = jax.random.PRNGKey(0)
    p_named = sharding.to_named(
        sharding.param_specs(jax.eval_shape(init, key), mesh), mesh)
    return jax.jit(init, out_shardings=p_named)(key)


def build_model_fns(params, cfg, *, max_seq, decode_tokens, index=None,
                    resume=False):
    """(prefill_fn, decode_fn, engine) for :func:`run_request_loop`.

    One construction shared by this launcher and the HTTP edge
    (``launch/httpd.py``).  With ``resume=True`` the pair comes from a
    :class:`PrefixResumeEngine` over ``index`` (which must carry a slab
    store); otherwise it is the plain jitted prefill/greedy-decode pair.
    Either way ``decode_fn`` RETURNS the ``(B, decode_tokens)`` greedy
    token array — the request loop surfaces it as
    ``RequestRecord.decoded`` (decoded output is never discarded).
    ``engine`` is ``None`` on the non-resume path."""
    if resume:
        engine = PrefixResumeEngine(params, cfg, max_seq=max_seq,
                                    index=index,
                                    decode_tokens=decode_tokens)
        prefill_fn, decode_fn = engine.request_fns()
        return prefill_fn, decode_fn, engine

    prefill_step = jax.jit(serve_step.make_prefill_step(cfg, max_seq))
    decode_step = jax.jit(serve_step.make_decode_step(cfg))

    def model_prefill(toks, hits):
        logits, cache = prefill_step(params, {"tokens": jnp.asarray(toks)})
        return logits, cache

    def model_decode(toks, state):
        logits, cache = state
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        outs = [np.asarray(nxt)]
        for t in range(decode_tokens - 1):
            pos = jnp.asarray(toks.shape[1] + t, jnp.int32)
            nxt, logits, cache = decode_step(params, cache, nxt, pos)
            outs.append(np.asarray(nxt))
        return np.concatenate(outs, axis=1)

    return model_prefill, model_decode, None


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--seq-shard-kv", action="store_true",
                    help="§Perf: split-KV decode cache layout")
    ap.add_argument("--no-resume", action="store_true",
                    help="disable the prefix-cache DECODE resume path "
                         "(index still counts hits, but every request "
                         "recomputes its full prefill) — the no-cache "
                         "reference behavior")
    # §6.2 durability knobs: the index derives its t_MWW admission window
    # from the lifetime target via the same formula as core/wear.py.
    ap.add_argument("--lifetime-years", type=float, default=None,
                    help="target index lifetime (enables the derived t_MWW "
                         "admission window; default: fixed window_ops)")
    ap.add_argument("--endurance", type=float, default=1e8,
                    help="cell endurance for --lifetime-years")
    ap.add_argument("--m-writes", type=int, default=3,
                    help="per-way write budget per t_MWW window")
    ap.add_argument("--ops-per-sec", type=float, default=1e6,
                    help="expected index op rate (cycle proxy) for "
                         "--lifetime-years under --wear-clock ops")
    ap.add_argument("--wear-clock", default="ops", choices=["ops", "wall"],
                    help="t_MWW cycle domain: 'ops' counts index ops (the "
                         "historic proxy), 'wall' makes the admission "
                         "window a wall-clock time budget (no op-rate "
                         "estimate needed)")
    # Index scaling knobs.
    ap.add_argument("--n-shards", type=int, default=1,
                    help="set-axis shards for the Monarch index (must "
                         "divide its n_sets; shards map onto the "
                         '("sets",) device mesh in contiguous blocks; '
                         "lookup stays ONE dispatch at any shard count)")
    ap.add_argument("--sync-admit", action="store_true",
                    help="admit inline on the serving loop instead of "
                         "behind the async AdmitQueue")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bound on fingerprints pending admission; None "
                         "(default) keeps the queue unbounded")
    ap.add_argument("--admit-policy", default="block",
                    choices=["block", "shed", "defer"],
                    help="back-pressure when --max-pending is hit: block "
                         "the submit, shed the oldest pending batch, or "
                         "defer (reject; the loop retries after decode)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = model_config(args)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))

    rng = np.random.default_rng(0)
    max_seq = args.prompt_len + args.decode_tokens
    # Prefix-cache DECODE resume: on when the arch supports it (attention
    # only).  The resume index hashes with chained prefix fingerprints
    # and carries the KV slab store the engine restores from.
    resume = not args.no_resume and transformer.resume_supported(cfg)
    fp_scheme = "prefix" if resume else "block"
    if args.lifetime_years is not None:
        kv_cfg = KVIndexConfig.with_lifetime(
            t_life_years=args.lifetime_years, endurance=args.endurance,
            ops_per_second=args.ops_per_sec, m_writes=args.m_writes,
            clock=args.wear_clock, n_sets=8, n_shards=args.n_shards,
            fingerprint=fp_scheme)
        unit = "ops" if args.wear_clock == "ops" else "us of wall time"
        print(f"[serve] lifetime target {args.lifetime_years}y @ "
              f"{args.endurance:.0e} endurance -> t_MWW window = "
              f"{kv_cfg.window_ops} {unit}, M={kv_cfg.m_writes}")
    else:
        kv_cfg = KVIndexConfig(n_sets=8, m_writes=args.m_writes,
                               clock=args.wear_clock, n_shards=args.n_shards,
                               fingerprint=fp_scheme)
    idx = MonarchKVIndex(kv_cfg,
                         slab_store=KVSlabStore() if resume else None)
    if not resume and not args.no_resume:
        print(f"[serve] resume path off: {cfg.name} has recurrent layers "
              "(prefix hits counted, prefill not skipped)")
    if args.n_shards > 1:
        placement = ("co-located, 1 device (collapsed to the unsharded "
                     "single-launch path)" if idx.set_mesh is None
                     else f"{idx.set_mesh}, single shard_map dispatch "
                          f"over {idx.n_parts} partitions")
        print(f"[serve] index sharded over {args.n_shards} set shards "
              f"({idx.sets_per_shard} sets each; {placement})")
    admit_q = AdmitQueue(idx, background=not args.sync_admit,
                         max_pending=args.max_pending,
                         policy=args.admit_policy)

    with mesh:
        params = init_placed_params(cfg, mesh)
        model_prefill, model_decode, engine = build_model_fns(
            params, cfg, max_seq=max_seq, decode_tokens=args.decode_tokens,
            index=idx, resume=resume)

        # shared prefix -> index hits after the first batch
        prefix = rng.integers(1, cfg.vocab_size,
                              args.prompt_len // 2).astype(np.int32)
        batches = []
        served = 0
        while served < args.requests:
            b = min(args.batch, args.requests - served)
            tails = rng.integers(
                1, cfg.vocab_size,
                (b, args.prompt_len - len(prefix))).astype(np.int32)
            batches.append(np.concatenate(
                [np.tile(prefix, (b, 1)), tails], axis=1))
            served += b
        # whole chunks of the shared prefix — 0 for short prompts, in
        # which case the per-batch report has no prefix column to average
        # (printing the empty-slice mean would be a NaN + RuntimeWarning)
        n_prefix_chunks = len(prefix) // CHUNK_TOKENS

        def report(i, toks, hits, rec):
            cached = (f"{hits[:, :n_prefix_chunks].mean():.0%}"
                      if n_prefix_chunks else "n/a")
            extra = (f", resumed {rec.resumed_chunks}/{rec.chunks} chunks"
                     if resume else "")
            # rec.decoded is the ACTUAL decode output (not the knob):
            # a decode path that stopped returning tokens shows up here.
            n_dec = (rec.decoded.shape[1] if rec.decoded is not None
                     else 0)
            print(f"[serve] batch of {toks.shape[0]}: prefix chunks cached "
                  f"{cached}{extra}, decoded {n_dec} tokens each")

        t0 = time.time()
        records = run_request_loop(admit_q, batches,
                                   prefill_fn=model_prefill,
                                   decode_fn=model_decode, on_batch=report)
        admit_q.close()                   # drain barrier before reporting
        dt = time.time() - t0
    s = idx.stats
    print(f"[serve] {served} requests in {dt:.1f}s; index hit rate "
          f"{idx.hit_rate:.1%}, {s.searches} CAM searches, "
          f"{s.admissions} admissions ({s.admit_calls} device calls), "
          f"{s.throttled} throttles")
    if resume:
        tot = engine.resumed_chunks + engine.computed_chunks
        print(f"[serve] resume: {engine.resumed_chunks}/{tot} prompt chunks "
              f"served from KV slabs "
              f"({idx.slab_store.resident_bytes / 1e6:.2f} MB resident)")
    aq = admit_q.stats
    print(f"[serve] admit queue: {aq.submitted} fps in {aq.batches} batches "
          f"({'inline' if args.sync_admit else 'async'}), "
          f"{aq.rww_flushes} read-your-writes flushes, "
          f"{aq.shed} batches shed, {aq.deferred} submits deferred")
    w = idx.wear_report()
    lt = idx.lifetime_estimate(endurance=args.endurance,
                               ops_per_second=args.ops_per_sec)
    print(f"[serve] wear: installs/set max {w['installs_per_set_max']:.0f} "
          f"(skew {w['skew_max_over_mean']:.2f}x mean), "
          f"{w['rotations']} rotations, "
          f"{w['throttled_sets_now']} sets at window budget; "
          f"projected lifetime {lt.years:.1f}y (ideal {lt.ideal_years:.1f}y)")
    return records


if __name__ == "__main__":
    main()
