"""HTTP serving launcher: the Monarch network edge.

    PYTHONPATH=src python -m repro.launch.httpd --arch yi-9b --reduced \
        --port 8077 --n-workers 2 --decode-tokens 8
    PYTHONPATH=src python -m repro.launch.httpd --arch yi-9b --n-layers 24

Boots the full serving stack — mesh-placed model, `MonarchKVIndex`
prefix cache (+ KV slab store on resume-capable archs), async
`AdmitQueue` — behind the stdlib HTTP edge from
:mod:`repro.serve.http_frontend`:

* ``POST /v1/generate`` with ``{"tokens": [[...], ...]}`` decodes
  through the shared index: prefix hits restore KV slabs and resume
  decode exactly as ``launch/serve.py`` does, because both run the same
  ``run_request_loop`` over the same model fns
  (:func:`repro.launch.serve.build_model_fns`).
* ``GET /healthz`` / ``GET /stats`` for probes and operators.
* N router workers micro-batch same-shape requests; the bounded router
  queue answers 429 + ``Retry-After`` under overload; SIGTERM/SIGINT
  triggers the graceful drain (503 on new requests, accepted ones and
  their admissions complete).

Index/durability knobs mirror ``launch/serve.py`` (the flag table in
docs/SERVING.md applies); the edge-specific knobs are ``--port`` /
``--host``, ``--n-workers``, ``--max-queue``, ``--batch-window-ms``.
``--port 0`` binds an ephemeral port and prints it — tests and the CI
smoke read the "listening on" line.
"""
from __future__ import annotations

import argparse
import signal
import threading
import time

import numpy as np

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.serve import (add_model_args, build_model_fns,
                                init_placed_params, model_config)
from repro.models import transformer
from repro.serve.admit_queue import AdmitQueue
from repro.serve.http_frontend import HttpFrontend, ServeRouter
from repro.serve.kv_index import (KVIndexConfig, KVSlabStore,
                                  MonarchKVIndex)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_model_args(ap)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--prompt-len", type=int, default=96,
                    help="max prompt tokens a request may carry (sizes "
                         "the decode cache)")
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--no-resume", action="store_true")
    # network edge
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8077,
                    help="0 binds an ephemeral port (printed at boot)")
    ap.add_argument("--n-workers", type=int, default=2,
                    help="router serving workers (each runs the shared "
                         "request loop on its micro-batches)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="router queue bound; a full queue answers 429 "
                         "with Retry-After")
    ap.add_argument("--batch-window-ms", type=float, default=2.0,
                    help="micro-batch window: same-shape requests "
                         "arriving within it share one prefill batch "
                         "(0 disables)")
    ap.add_argument("--verbose", action="store_true",
                    help="per-request access log")
    # index scaling / durability (same semantics as launch/serve.py)
    ap.add_argument("--n-shards", type=int, default=1)
    ap.add_argument("--sync-admit", action="store_true")
    ap.add_argument("--max-pending", type=int, default=None)
    ap.add_argument("--admit-policy", default="block",
                    choices=["block", "shed", "defer"])
    ap.add_argument("--admit-after-reads", type=int, default=1,
                    help="no-allocate filter: offers before install "
                         "(0 = admit on first touch; short-lived smoke "
                         "servers want 0 so repeats hit immediately)")
    ap.add_argument("--wear-clock", default="wall",
                    choices=["ops", "wall"],
                    help="t_MWW cycle domain (the edge defaults to "
                         "'wall': serving traffic is bursty, so the "
                         "admission window should be a real time "
                         "budget)")
    ap.add_argument("--lifetime-years", type=float, default=None)
    ap.add_argument("--endurance", type=float, default=1e8)
    ap.add_argument("--m-writes", type=int, default=3)
    ap.add_argument("--ops-per-sec", type=float, default=1e6)
    return ap


def build_frontend(args) -> tuple[HttpFrontend, AdmitQueue]:
    """Model + index + router + socket, not yet started.

    Separated from :func:`main` so tests can boot the real stack on an
    ephemeral port and drive it in-process."""
    cfg = model_config(args)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    max_seq = args.prompt_len + args.decode_tokens

    resume = not args.no_resume and transformer.resume_supported(cfg)
    fp_scheme = "prefix" if resume else "block"
    kv_kw = dict(n_sets=8, m_writes=args.m_writes, clock=args.wear_clock,
                 n_shards=args.n_shards, fingerprint=fp_scheme,
                 admit_after_reads=args.admit_after_reads)
    if args.lifetime_years is not None:
        kv_cfg = KVIndexConfig.with_lifetime(
            t_life_years=args.lifetime_years, endurance=args.endurance,
            ops_per_second=args.ops_per_sec, **kv_kw)
    else:
        kv_cfg = KVIndexConfig(**kv_kw)
    idx = MonarchKVIndex(kv_cfg,
                         slab_store=KVSlabStore() if resume else None)
    admit_q = AdmitQueue(idx, background=not args.sync_admit,
                         max_pending=args.max_pending,
                         policy=args.admit_policy)

    with mesh:
        params = init_placed_params(cfg, mesh)
        prefill_fn, decode_fn, _ = build_model_fns(
            params, cfg, max_seq=max_seq,
            decode_tokens=args.decode_tokens, index=idx, resume=resume)
        # one throwaway prefill compiles the hot path before the socket
        # opens, so the first real request doesn't pay the jit
        warm = np.ones((1, min(args.prompt_len, 16)), np.int32)
        state = prefill_fn(warm, None if resume
                           else np.zeros((1, 0), bool))
        jax.block_until_ready(jax.tree.leaves(
            state.state["logits"] if resume else state[0]))

    router = ServeRouter(
        admit_q, prefill_fn=prefill_fn, decode_fn=decode_fn,
        n_workers=args.n_workers, max_queue=args.max_queue,
        batch_window_s=args.batch_window_ms / 1e3)
    frontend = HttpFrontend(router, host=args.host, port=args.port,
                            verbose=args.verbose)
    print(f"[httpd] {cfg.name}: resume "
          f"{'ON' if resume else 'off'}, index n_shards={args.n_shards}, "
          f"admit policy={args.admit_policy} "
          f"max_pending={args.max_pending}, wear clock={args.wear_clock}")
    return frontend, admit_q


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    frontend, admit_q = build_frontend(args)
    frontend.start()
    host, port = frontend.address
    print(f"[httpd] listening on http://{host}:{port} "
          f"({args.n_workers} workers, queue bound {args.max_queue}, "
          f"batch window {args.batch_window_ms:g} ms)", flush=True)

    stop = threading.Event()

    def _graceful(signum, frame):
        print(f"[httpd] signal {signum}: draining "
              "(new requests -> 503)", flush=True)
        # refuse new work IMMEDIATELY; the full drain runs on the main
        # thread below (signal handlers must stay tiny)
        frontend.begin_shutdown()
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    stop.wait()
    t0 = time.monotonic()
    frontend.shutdown()                  # drain router + admissions
    admit_q.close()
    idx = admit_q.index
    r = frontend.router.stats
    print(f"[httpd] drained in {time.monotonic() - t0:.2f}s: "
          f"{r.completed} served / {r.errors} errors / "
          f"{r.rejected_busy} busy-rejected / "
          f"{r.rejected_closed} drain-rejected; "
          f"index hit rate {idx.hit_rate:.1%}, "
          f"{idx.stats.admissions} admissions", flush=True)


if __name__ == "__main__":
    main()
