"""Find the highest open-loop rate a cell's serving stack sustains.

    python3 chipbench/sweep.py --seconds 30 --seed 500 \
        --rate yi9b.docqa-shared=0.5,1,2,3,4,5

Boots the stack once (the cells given must share a configuration and
prompt lengths), fills one pool of prefixes, then for each cell and
rate offers one window of open-loop traffic at that rate and prints one JSON
line: requests, answered, refused, p50/p95 latency from the schedule,
the p95 of the window's first and last thirds (a growing backlog shows
as the last third far above the first), mean micro-batch rows and how
late the generator sent.  A last line per cell names the knee: the
highest rate at which every request is answered, the backlog does not
grow (last third's p95 within 1.2 of the first's) and the p95 stays
within twice the unloaded latency, the median at the lowest rate swept
(so sweep from a rate at which requests seldom meet), and 0.8 of it,
the rate for a cell whose tails are judged.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _stats(recs, seconds: float, rate: float) -> dict:
    from chipbench.harness import DRAIN_S, quantile
    lat = [((r.done - r.due) if r.status == 200
            else seconds + DRAIN_S - r.due) * 1e3 for r in recs]
    third = [[lat[i] for i, r in enumerate(recs)
              if k * seconds / 3 <= r.due < (k + 1) * seconds / 3]
             for k in range(3)]
    ok = [r for r in recs if r.status == 200]
    return {"rate_rps": rate, "requests": len(recs), "answered": len(ok),
            "refused": sum(r.status in (429, 503) for r in recs),
            "p50_ms": quantile(lat, 0.5), "p95_ms": quantile(lat, 0.95),
            "p95_first_third_ms": quantile(third[0], 0.95),
            "p95_last_third_ms": quantile(third[2], 0.95),
            "mean_rows": (sum(r.doc["batched_rows"] for r in ok) / len(ok)
                          if ok else 0.0),
            "send_late_p95_ms": quantile([(r.sent - r.due) * 1e3
                                          for r in recs], 0.95)}


def knee(lines: list) -> dict:
    """The knee of one cell's sweep lines, and 0.8 of it."""
    lines = sorted(lines, key=lambda r: r["rate_rps"])
    unloaded = lines[0]["p50_ms"]
    k = None
    for r in lines:
        if (r["answered"] < r["requests"]
                or r["p95_last_third_ms"] > 1.2 * r["p95_first_third_ms"]
                or r["p95_ms"] > 2 * unloaded):
            break
        k = r["rate_rps"]
    return {"unloaded_ms": unloaded, "knee_rps": k,
            "rate_rps": None if k is None else round(0.8 * k, 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rate", action="append", required=True,
                    help="<workload>=<rate>,<rate>,...")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=500)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_compile_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness, traffic

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the sweep measures the chip: no TPU found")
    sweeps = []
    for spec in args.rate:
        name, rates = spec.split("=")
        sweeps.append((harness.load_cell(name, ROOT),
                       [float(r) for r in rates.split(",")]))
    first = sweeps[0][0]
    found = {}
    frontend, admit_q = harness.boot(first.cfg, first.mix)
    stream = 0
    for cell, rates in sweeps:
        if (cell.spec["config"] != first.spec["config"]
                or traffic.prompt_tokens(cell.mix)
                != traffic.prompt_tokens(first.mix)
                or cell.mix["decode_tokens"] != first.mix["decode_tokens"]):
            raise SystemExit(f"{cell.name} needs a stack of its own")
        for k, rate in enumerate(rates):
            # One document pool for every rate (a full index cannot take
            # a second pool: new entries would evict each other); each
            # rate sends fresh questions from a token stream of its own.
            mix = dict(cell.mix, rate_rps=rate)
            plan = traffic.build(mix, cell.cfg["vocab_size"], args.seed,
                                 args.seconds)
            plan.stream = 10 + stream
            stream += 1
            harness.fill(frontend, admit_q, plan)
            if k == 0:
                harness.warm(frontend, admit_q, plan)
            t0 = harness.time.perf_counter()
            recs = harness.drive_open(frontend.address[1], plan,
                                      args.seconds, t0)
            line = {"workload": cell.name,
                    **_stats(recs, args.seconds, rate)}
            found.setdefault(cell.name, []).append(line)
            print(json.dumps(line), flush=True)
    frontend.shutdown()
    admit_q.close()
    for name, lines in found.items():
        print(json.dumps({"workload": name, **knee(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
