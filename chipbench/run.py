"""Run one benchmark cell on the chip this process finds.

    python3 chipbench/run.py --workload yi9b.docqa-shared --seed 7 \
        --seconds 40 --trace 0

Boots the program's serving stack for the cell named in
``BENCHMARK.json``, warms it, offers the cell's traffic for ``--seconds``,
checks a sample of the answers against the float32 reference, and prints
one JSON object as the last line of standard output: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics (from a
profiler trace of the window) with ``--trace 1``.  The numbers compared
for ``correct`` come last, on standard error and under ``checks``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's compilation cache lives in
``.jax_compile_cache/`` of this checkout, so only a checkout's first run
of a cell compiles.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()       # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_compile_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The cache is this checkout's, whatever the environment names.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness

    cell = harness.load_cell(args.workload, ROOT)
    trace_dir = TRACE_DIR / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), trace_dir=trace_dir,
                         started=STARTED)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
