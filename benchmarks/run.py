"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --autotune   # block-shape cache

Modules (paper artifact -> bench):
    Table 1        -> table1_tech        (32KB block technology study, §5)
    Fig. 9/10      -> fig9_cache         (cache-mode perf + hit rates, C1-C4)
    Fig. 11        -> fig11_lifetime     (M=3 lifetime vs ideal leveling, C7/C8)
    Figs. 12-14    -> fig12_14_hashing   (hopscotch/YCSB flat-CAM, C5)
    §10.5          -> string_match       (Phoenix String-Match, C6)
    kernels        -> kernels_bench      (Pallas kernels us/call + KV index
                                          lookup/admit + wear-op microbench)
    front end      -> serve_bench        (open-loop request latency: Poisson
                                          + burst-trace arrivals, p50/p99,
                                          goodput, shed rate)
    decode path    -> decode_bench       (prefix-cache resume vs no-cache:
                                          decode tokens/s, hit rate,
                                          token-identity)
    §Roofline      -> roofline_summary   (dry-run three-term table)

Each module appends ``name,us_per_call,derived`` CSV rows; the combined CSV
lands in benchmarks/results.csv.  The figure modules additionally emit
machine-readable ``BENCH_<name>.json`` artifacts (see ``repro.bench``);
``--quick`` selects the CI-sized sweep policy from
``repro.bench.harness.BenchSizes``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from repro.bench import BenchSizes
from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (decode_bench, fig9_cache, fig11_lifetime,
                        fig12_14_hashing, kernels_bench, roofline_summary,
                        serve_bench, string_match, table1_tech)

CSV_PATH = os.path.join(os.path.dirname(__file__), "results.csv")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="run a single module by name")
    ap.add_argument("--autotune", action="store_true",
                    help="regenerate the kernel block-shape cache "
                         "(src/repro/kernels/autotune_cache.json) instead "
                         "of running the benches")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.autotune:
        from repro.kernels import autotune
        payload = autotune.autotune(quick=args.quick)
        for key in sorted(payload["families"]):
            fam = payload["families"][key]
            shape = f"block_q={fam['block_q']}"
            if "block_c" in fam:
                shape += f" block_c={fam['block_c']}"
            print(f"[autotune] {key}: {shape} ({fam['median_us']} us)")
        print(f"[autotune] wrote {autotune.DEFAULT_CACHE_PATH} "
              f"(fingerprint {autotune.cache_fingerprint()})")
        return
    sizes = BenchSizes(quick=args.quick)

    benches = [
        ("table1_tech", lambda rows: table1_tech.run(rows)),
        ("kernels_bench", lambda rows: kernels_bench.run(
            rows, quick=args.quick)),
        ("fig9_cache", lambda rows: fig9_cache.run(
            rows, n_requests=sizes.fig_requests, systems=sizes.systems,
            quick=args.quick)),
        ("fig11_lifetime", lambda rows: fig11_lifetime.run(
            rows, n_requests=sizes.fig_requests, quick=args.quick)),
        ("fig12_14_hashing", lambda rows: fig12_14_hashing.run(
            rows, quick=args.quick)),
        ("serve_bench", lambda rows: serve_bench.run(rows, quick=args.quick)),
        ("decode_bench", lambda rows: decode_bench.run(
            rows, quick=args.quick)),
        ("string_match", lambda rows: string_match.run(rows)),
        ("roofline_summary", lambda rows: roofline_summary.run(rows)),
    ]
    if args.only and args.only not in {n for n, _ in benches}:
        ap.error(f"--only {args.only!r}: unknown module "
                 f"(choose from {', '.join(n for n, _ in benches)})")

    rows: list[str] = ["name,us_per_call,derived"]
    failures = []
    t_all = time.time()
    for name, fn in benches:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"\n{'=' * 72}\n[bench] {name}\n{'=' * 72}")
        try:
            fn(rows)
            print(f"[bench] {name} done in {time.time() - t0:.1f}s")
        except Exception as e:  # keep the harness going; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    with open(CSV_PATH, "w") as f:
        f.write("\n".join(rows) + "\n")
    print(f"\n[bench] all done in {time.time() - t_all:.1f}s; "
          f"{len(rows) - 1} CSV rows -> {CSV_PATH}")
    if failures:
        print("[bench] FAILURES:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
