"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/calibrate.py --workload yi9b.docqa-shared \
        --seeds 12 --control-seeds 3 --seconds 15 --dump gaps.npz

Boots the cell's stack once, fills it with the first seed's prefixes
and, for each seed, offers a window of the cell's own traffic as a run
does (the seed's fresh tokens and order over that one prefix pool),
then keeps the sample of answered requests that a run would check.  Once
the stack is shut down and its memory freed, the float32 reference reads
each seed's served-token gaps (the lower readings: the largest over
seeds sets them), and each control, the reference with its weights
rounded to int8 and to float8 e4m3, reads at every served position the
gap of the token it would put first (the upper readings: the smallest
over seeds).  One JSON line per seed, then a summary line; ``--dump``
keeps every gap, per seed and reader, in an ``.npz`` file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
#: The controls: the nearest precisions below the bfloat16 served.
QUANTS = ("int8", "fp8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 9000)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_compile_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness, traffic

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration reads the chip: no TPU found")
    cell = harness.load_cell(args.workload, ROOT)
    frontend, admit_q = harness.boot(cell.cfg, cell.mix)
    samples, prefixes = [], None
    for k in range(args.seeds):
        seed = args.first_seed + k
        plan = traffic.build(cell.mix, cell.cfg["vocab_size"], seed,
                             args.seconds)
        # The first seed's prefixes serve every seed: a full index cannot
        # take a second pool (new entries would evict each other), so the
        # seeds differ in their fresh tokens and order, not in the pool.
        if prefixes is None:
            prefixes = plan.prefixes
        plan.prefixes = prefixes
        harness.fill(frontend, admit_q, plan)
        if k == 0:
            harness.warm(frontend, admit_q, plan)
        t0 = harness.time.perf_counter()
        drive = (harness.drive_open if cell.mix["loop"] == "open"
                 else harness.drive_closed)
        recs = drive(frontend.address[1], plan, args.seconds, t0)
        ok = [r for r in recs if r.due < args.seconds and r.status == 200]
        samples.append((seed, *harness.sample(cell, plan, ok, seed)))
    frontend.shutdown()
    admit_q.close()
    del frontend, admit_q
    gc.collect()
    jax.clear_caches()

    readings, dump = [], {}
    for k, (seed, prompts, served) in enumerate(samples):
        ref = harness.reference_logits(cell, prompts, served)
        gaps = {"program": harness.served_gap(ref, served)}
        if k < args.control_seeds:
            for q in QUANTS:
                ctl = harness.reference_logits(cell, prompts, served, quant=q)
                gaps[q] = harness.served_gap(ref, ctl.argmax(-1))
        line = {"seed": seed}
        for who, g in gaps.items():
            line[who] = harness.gap_stats(g)
            dump[f"{who}_{seed}"] = g
        readings.append(line)
        print(json.dumps(line), flush=True)
    if args.dump:
        np.savez_compressed(args.dump, **dump)
    summary = {"workload": cell.name}
    for stat in harness.GAP_STATS:
        summary[f"lower_{stat}"] = max(r["program"][stat] for r in readings)
        for q in QUANTS:
            summary[f"upper_{q}_{stat}"] = min(
                (r[q][stat] for r in readings if q in r), default=None)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
