"""Measured block-shape selection for the XAM kernels.

The block shapes used to be a hard-coded two-point heuristic (the 16/64
``_pick_block_q`` switch plus ``DEFAULT_BLOCK_Q``/``DEFAULT_BLOCK_C``).
This module replaces the constants with MEASURED winners: a small sweep
(`autotune()` — ``python -m benchmarks.run --autotune`` or ``python -m
repro.kernels.autotune``) times MXU-aligned candidates per family and
commits the winners to ``autotune_cache.json`` next to this file.

A *family* is ``{kernel}/{backend}/{plane_format}/{shape_bucket}``:

* ``kernel`` — ``xam_multiset`` (the fused serving kernel; tunes
  ``block_q``) or ``xam_search`` (the flat bitmap kernel; tunes
  ``(block_q, block_c)``);
* ``backend`` — ``jax.default_backend()`` at sweep time (``cpu``
  interpret-mode numbers must never steer a TPU run and vice versa);
* ``plane_format`` — ``int8`` / ``packed8`` (``kernels/common.py``):
  packed planes shift the bandwidth/compute balance, so they tune
  separately;
* ``shape_bucket`` — for ``xam_multiset`` the SAME two-point structure
  the old switch had (``narrow`` below ``WIDE_BLOCK_AT`` queries,
  ``wide`` at/above), for ``xam_search`` a single ``default`` bucket.
  Keeping the bucket structure is what caps jit-cache growth at the
  existing pow2 buckets: every batch in a bucket maps to ONE
  deterministic block shape, cache hit or not.

Misses fall back DETERMINISTICALLY to today's constants, so a cold cache
(deleted file, fresh machine, unknown backend) produces bit-identical
kernel *results* — block shapes never change an answer, only its speed —
and the same compiled-shape count.  ``REPRO_AUTOTUNE_CACHE`` points the
loader at an alternate cache file (CI uses it to prove the cold path).
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import pathlib

from repro.kernels.common import resolve_plane_format
from repro.kernels.xam_search.kernel import (
    DEFAULT_BLOCK_C, DEFAULT_BLOCK_Q, MULTISET_BLOCK_Q)

#: Committed winners; regenerate with ``python -m benchmarks.run --autotune``.
DEFAULT_CACHE_PATH = pathlib.Path(__file__).with_name("autotune_cache.json")

#: Env knob pointing the loader at an alternate cache file (cold-cache CI).
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

#: Adaptive query-block threshold — the shape-bucket split of the
#: ``xam_multiset`` families AND the deterministic fallback's switch
#: point (the pre-autotune heuristic: ``MULTISET_BLOCK_Q`` below it,
#: ``WIDE_BLOCK_Q`` at/above).  Search results are layout-independent
#: (first-valid-way per query), so the width never changes an answer.
WIDE_BLOCK_AT = 256
WIDE_BLOCK_Q = 64

#: MXU-aligned sweep candidates: block_q multiples of 8 (sublanes, floor
#: 8), block_c multiples of 128 (lanes).
BLOCK_Q_CANDIDATES = (8, 16, 32, 64, 128)
BLOCK_C_CANDIDATES = (128, 256, 512)


def cache_path() -> pathlib.Path:
    override = os.environ.get(CACHE_ENV)
    return pathlib.Path(override) if override else DEFAULT_CACHE_PATH


@functools.lru_cache(maxsize=None)
def _load(path_str: str) -> dict:
    """Family table from the cache file; {} when cold/unreadable (the
    deterministic fallback then answers every query)."""
    path = pathlib.Path(path_str)
    if not path.exists():
        return {}
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return {}
    fams = data.get("families", {})
    return fams if isinstance(fams, dict) else {}


def _families() -> dict:
    return _load(str(cache_path()))


def reset_cache() -> None:
    """Drop the in-process loader cache (tests repoint REPRO_AUTOTUNE_CACHE
    and need the next consult to re-read)."""
    _load.cache_clear()


def _backend() -> str:
    import jax
    return jax.default_backend()


def family_key(kernel: str, plane_format: str, shape_bucket: str) -> str:
    return f"{kernel}/{_backend()}/{plane_format}/{shape_bucket}"


def multiset_block_q(n_queries: int, plane_format: str = "int8") -> int:
    """Measured ``block_q`` for the fused multiset kernel, deterministic
    per (shape bucket, plane format): the committed winner when the
    family is cached, else the pre-autotune two-point heuristic."""
    plane_format = resolve_plane_format(plane_format)
    wide = n_queries >= WIDE_BLOCK_AT
    fam = _families().get(
        family_key("xam_multiset", plane_format, "wide" if wide else "narrow"))
    if fam is not None:
        return int(fam["block_q"])
    return WIDE_BLOCK_Q if wide else MULTISET_BLOCK_Q


def search_blocks(plane_format: str = "int8") -> tuple[int, int]:
    """Measured ``(block_q, block_c)`` for the flat bitmap kernel, with a
    deterministic fallback to the module defaults."""
    plane_format = resolve_plane_format(plane_format)
    fam = _families().get(family_key("xam_search", plane_format, "default"))
    if fam is not None:
        return int(fam["block_q"]), int(fam["block_c"])
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_C


def cache_fingerprint() -> str:
    """Short content hash of the active cache file — stamped into every
    ``BENCH_*.json`` so cross-run comparisons can't silently mix tuned
    and untuned (or differently tuned) configurations.  ``"cold"`` when
    the file is absent."""
    path = cache_path()
    if not path.exists():
        return "cold"
    return hashlib.sha256(path.read_bytes()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# The sweep.
# ---------------------------------------------------------------------------

def _time_multiset(n_q: int, block_q: int, plane_format: str,
                   reps: int) -> float:
    """Median us for one synthetic multiset workload at a candidate
    block_q (the bench's own shape family: 8 sets, 32-bit keys, 512
    ways)."""
    import numpy as np

    import jax

    from repro.bench.harness import time_callable
    from repro.kernels.common import pack_bits_np
    from repro.kernels.xam_search import ops as xam_ops
    from repro.kernels.xam_search.kernel import xam_search_multiset_pallas

    rng = np.random.default_rng(0)
    n_sets, r, c = 8, 32, 512
    planes_np = rng.integers(0, 2, (n_sets, r, c)).astype(np.int8)
    if plane_format == "packed8":
        planes = jax.numpy.asarray(pack_bits_np(planes_np, axis=1))
    else:
        planes = jax.numpy.asarray(planes_np)
    valid = jax.numpy.asarray(
        rng.integers(0, 2, (n_sets, c)).astype(np.int8))
    set_ids = rng.integers(0, n_sets, n_q)
    key_bits = xam_ops.words_to_bits_np(
        rng.integers(0, 2 ** 32, n_q, dtype=np.uint32), r)
    slot, block_sets, padded_q, n_blocks = xam_ops.group_queries_by_set(
        set_ids, n_sets, block_q)
    keys_p = np.zeros((padded_q, r), np.int8)
    masks_p = np.zeros((padded_q, r), np.int8)
    keys_p[slot] = key_bits
    masks_p[slot] = 1
    live = (np.arange(len(block_sets)) < n_blocks).astype(np.int32)
    args = tuple(jax.numpy.asarray(a)
                 for a in (keys_p, masks_p, block_sets, live))
    def run():
        return xam_search_multiset_pallas(
            args[0], args[1], planes, valid, args[2], args[3],
            block_q=block_q).block_until_ready()

    return time_callable(run, reps=reps).median_us


def _time_search(block_q: int, block_c: int, plane_format: str,
                 reps: int) -> float:
    """Median us for the flat bitmap kernel at a candidate block pair."""
    import numpy as np

    import jax

    from repro.bench.harness import time_callable
    from repro.kernels.common import pack_bits_np
    from repro.kernels.xam_search.kernel import xam_search_pallas

    rng = np.random.default_rng(0)
    q, r, c = 64, 64, 512
    keys = jax.numpy.asarray(rng.integers(0, 2, (q, r)).astype(np.int8))
    masks = jax.numpy.ones((q, r), jax.numpy.int8)
    data_np = rng.integers(0, 2, (r, c)).astype(np.int8)
    if plane_format == "packed8":
        data = jax.numpy.asarray(pack_bits_np(data_np, axis=0))
    else:
        data = jax.numpy.asarray(data_np)
    def run():
        return xam_search_pallas(
            keys, data, masks, block_q=block_q,
            block_c=block_c).block_until_ready()

    return time_callable(run, reps=reps).median_us


def autotune(out_path: pathlib.Path | str | None = None,
             quick: bool = False) -> dict:
    """Sweep every family on THIS backend and write the winners.

    Returns the full cache payload (also written to ``out_path``, default
    the committed ``autotune_cache.json``).  Winners are medians via
    ``bench/harness.time_callable``; re-running on the same rig
    reproduces the same table up to timing noise on near-tied candidates.
    """
    from repro.bench.harness import time_callable  # noqa: F401 (doc anchor)

    reps = 3 if quick else 5
    backend = _backend()
    families: dict[str, dict] = {}
    # xam_multiset: one representative batch size per shape bucket — the
    # bucket's winner must be deterministic across every size in it, so
    # one size per bucket is the contract, not a shortcut.
    bucket_sizes = {"narrow": 128, "wide": 512}
    for plane_format in ("int8", "packed8"):
        for bucket, n_q in bucket_sizes.items():
            timings = {
                bq: _time_multiset(n_q, bq, plane_format, reps)
                for bq in BLOCK_Q_CANDIDATES}
            best = min(timings, key=timings.get)
            families[f"xam_multiset/{backend}/{plane_format}/{bucket}"] = {
                "block_q": best,
                "median_us": round(timings[best], 3),
                "swept": {str(k): round(v, 3) for k, v in timings.items()},
            }
        timings = {
            (bq, bc): _time_search(bq, bc, plane_format, reps)
            for bq in BLOCK_Q_CANDIDATES for bc in BLOCK_C_CANDIDATES}
        best = min(timings, key=timings.get)
        families[f"xam_search/{backend}/{plane_format}/default"] = {
            "block_q": best[0], "block_c": best[1],
            "median_us": round(timings[best], 3),
            "swept": {f"{k[0]}x{k[1]}": round(v, 3)
                      for k, v in timings.items()},
        }
    payload = {
        "version": 1,
        "backend": backend,
        "block_q_candidates": list(BLOCK_Q_CANDIDATES),
        "block_c_candidates": list(BLOCK_C_CANDIDATES),
        "families": families,
    }
    path = pathlib.Path(out_path) if out_path else DEFAULT_CACHE_PATH
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    reset_cache()
    return payload


def main() -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--quick", action="store_true", help="3 reps instead of 5")
    p.add_argument("--out", default=None,
                   help="cache file to write (default: the committed one)")
    args = p.parse_args()
    payload = autotune(args.out, quick=args.quick)
    for key in sorted(payload["families"]):
        fam = payload["families"][key]
        shape = f"block_q={fam['block_q']}"
        if "block_c" in fam:
            shape += f" block_c={fam['block_c']}"
        print(f"[autotune] {key}: {shape} ({fam['median_us']} us)")
    print(f"[autotune] wrote {args.out or DEFAULT_CACHE_PATH} "
          f"(fingerprint {cache_fingerprint()})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
