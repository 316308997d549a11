"""Kernel micro-benchmarks: us/call of the three Pallas kernels (interpret
mode on this CPU rig; the numbers are CI-tracking, not TPU projections) and
of the MonarchKVIndex batched prefix lookup — the device-resident CAM fast
path (one fused multi-set launch per batch).  Timing discipline (warmup,
median-of-k, block_until_ready) comes from ``repro.bench.harness``.

``benchmarks/check_regression.py`` compares the emitted medians against the
committed ``benchmarks/baselines/BENCH_kernels.json``.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

import jax

from repro.bench import (BenchSizes, emit_json, time_callable,
                         time_interleaved)
from repro.core import wear
from repro.kernels.common import pack_bits_np
from repro.kernels.hopscotch import ops as hop_ops
from repro.kernels.string_match import ops as sm_ops
from repro.kernels.xam_search import ops as xam_ops
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import KVIndexConfig, MonarchKVIndex


def run(csv_rows: list[str], quick: bool = False):
    rng = np.random.default_rng(0)
    reps = BenchSizes(quick=quick).kernel_reps
    print("\n== kernel micro-benchmarks (CPU interpret mode) ==")
    timings = {}

    keys = rng.integers(0, 2, (64, 64)).astype(np.int8)
    data = rng.integers(0, 2, (64, 512)).astype(np.int8)
    t = time_callable(lambda: xam_ops.xam_search(keys, data), reps=reps)
    timings["xam_search"] = t
    print(f"xam_search 64q x (64x512): {t.median_us:.0f} us")
    csv_rows.append(f"kernel_xam_search,{t.median_us:.0f},64x512")

    # fused multi-set search: 128 queries over 8 device-resident planes
    n_sets, r, c = 8, 32, 512
    planes = jnp.asarray(rng.integers(0, 2, (n_sets, r, c)).astype(np.int8))
    valid = jnp.asarray(rng.integers(0, 2, (n_sets, c)).astype(np.int8))
    m_words = rng.integers(0, 2 ** 32, 128, dtype=np.uint32)
    m_sets = rng.integers(0, n_sets, 128).astype(np.int32)
    m_bits = xam_ops.words_to_bits_np(m_words, r)

    # The int8 and PACKED (plane_format="packed8": 8 bits per uint8 word
    # along R, unpacked in VMEM per tile) variants of the same workload.
    # Results are bit-identical, plane traffic is 8x lower;
    # check_regression.py gates the packed median against both the
    # same-run int8 median and the committed baseline, so the pair is
    # timed INTERLEAVED with a higher rep floor than the rest of the
    # quick sweep — at reps=3 back-to-back, interpret-mode medians
    # wobble ~20% run-to-run, more than the packed win being gated.
    planes_packed = jnp.asarray(pack_bits_np(np.asarray(planes), axis=1))
    out_p = xam_ops.xam_search_multiset(m_bits, m_sets, planes_packed, valid)
    out_i = xam_ops.xam_search_multiset(m_bits, m_sets, planes, valid)
    assert np.array_equal(np.asarray(out_p), np.asarray(out_i)), \
        "packed planes must be bit-identical to int8 planes"
    t, tp = time_interleaved(
        [lambda: xam_ops.xam_search_multiset(m_bits, m_sets, planes, valid),
         lambda: xam_ops.xam_search_multiset(
             m_bits, m_sets, planes_packed, valid)],
        warmup=3, reps=max(reps, 11))
    timings["xam_multiset"] = t
    print(f"xam_multiset 128q x 8 sets (32x512): {t.median_us:.0f} us")
    csv_rows.append(f"kernel_xam_multiset,{t.median_us:.0f},8x32x512")
    timings["xam_multiset_packed"] = tp
    print(f"xam_multiset_packed 128q x 8 sets (4x512 words): "
          f"{tp.median_us:.0f} us -> {t.median_us / tp.median_us:.2f}x vs "
          f"int8 planes (bit-identical)")
    csv_rows.append(f"kernel_xam_multiset_packed,{tp.median_us:.0f},"
                    f"8x4x512w")

    h, n = 32, 32 * 256
    t_lo = rng.integers(0, 2 ** 32, n, dtype=np.uint32)
    t_hi = rng.integers(0, 2 ** 32, n, dtype=np.uint32)
    homes = rng.integers(0, n - 2 * h, 64).astype(np.int32)
    q = rng.integers(0, 2 ** 32, 64, dtype=np.uint32)
    t = time_callable(
        lambda: hop_ops.hopscotch_lookup(t_lo, t_hi, homes, q, q, window=h),
        reps=reps)
    timings["hopscotch_lookup"] = t
    print(f"hopscotch_lookup 64q w32: {t.median_us:.0f} us")
    csv_rows.append(f"kernel_hopscotch,{t.median_us:.0f},w32")

    text = rng.integers(97, 113, 1 << 16).astype(np.uint8)
    pat = text[1000:1012].copy()
    t = time_callable(lambda: sm_ops.string_match(text, pat, tile=4096),
                      reps=reps)
    timings["string_match"] = t
    print(f"string_match 64KiB p12: {t.median_us:.0f} us")
    csv_rows.append(f"kernel_string_match,{t.median_us:.0f},64KiB")

    idx = MonarchKVIndex(KVIndexConfig(n_sets=8))
    toks = rng.integers(1, 1000, (4, 256)).astype(np.int32)
    idx.admit(toks)
    idx.admit(toks)   # second touch -> admitted
    t = time_callable(lambda: idx.lookup(toks), warmup=1, reps=reps)
    timings["kv_index_lookup"] = t
    print(f"kv_index lookup 4x256 tokens: {t.median_us:.0f} us "
          f"(hit rate {idx.hit_rate:.2f}, "
          f"{idx.stats.searches} launches/{idx.stats.lookups} lookups)")
    csv_rows.append(f"kv_index_lookup,{t.median_us:.0f},{idx.hit_rate:.2f}")

    # batch scaling: one launch regardless of batch width
    toks_big = rng.integers(1, 4000, (32, 512)).astype(np.int32)
    idx.admit(toks_big)
    idx.admit(toks_big)
    t = time_callable(lambda: idx.lookup(toks_big), warmup=1, reps=reps)
    timings["kv_index_lookup_32x512"] = t
    print(f"kv_index lookup 32x512 tokens: {t.median_us:.0f} us "
          f"({t.median_us / (32 * 512 // 16):.1f} us/chunk)")
    csv_rows.append(f"kv_index_lookup_32x512,{t.median_us:.0f},")

    # set-sharded lookup: same 32x512 batch at n_shards=4, ONE device
    # dispatch regardless of the shard count (the stacked shard_map path
    # on a ("sets",) mesh; collapsed to the single fused launch on a
    # 1-device host).
    idx_s = MonarchKVIndex(KVIndexConfig(n_sets=8, n_shards=4))
    idx_s.admit(toks_big)
    idx_s.admit(toks_big)
    t = time_callable(lambda: idx_s.lookup(toks_big), warmup=1, reps=reps)
    timings["kv_index_lookup_sharded"] = t
    print(f"kv_index lookup 32x512 tokens, 4 set shards "
          f"({idx_s.n_parts} partitions): {t.median_us:.0f} us "
          f"({idx_s.stats.searches} dispatches/"
          f"{idx_s.stats.lookups} lookups)")
    csv_rows.append(f"kv_index_lookup_sharded,{t.median_us:.0f},4shards")

    # device-resident rotation: the set+7 remap (donated roll + ppermute
    # boundary exchange across partitions; pure donated roll when
    # collapsed) — plane data never moves through the host.
    t = time_callable(lambda: idx_s._rotate(), warmup=1, reps=reps)
    timings["kv_index_rotate"] = t
    print(f"kv_index rotate (device remap, 4 shards): {t.median_us:.0f} us")
    csv_rows.append(f"kv_index_rotate,{t.median_us:.0f},4shards")

    # batched admission: ONE jitted device call per 64-fingerprint batch.
    # Fresh unique fingerprints every rep so the install path (not the
    # resident fast path) is what's timed.
    n_fp, n_batches = 64, reps + 4
    all_fps = (1 + np.arange(n_fp * n_batches, dtype=np.uint32))
    fp_batches = iter(np.split(all_fps, n_batches))
    idx_b = MonarchKVIndex(KVIndexConfig(n_sets=8, admit_after_reads=0))
    t = time_callable(lambda: idx_b.admit_fps(next(fp_batches)),
                      warmup=2, reps=reps)
    timings["kv_index_admit"] = t
    assert idx_b.stats.admit_calls == reps + 2   # O(1) calls per batch
    print(f"kv_index admit 64 fps (batched): {t.median_us:.0f} us "
          f"({t.median_us / n_fp:.1f} us/install)")
    csv_rows.append(f"kv_index_admit,{t.median_us:.0f},64fp")

    # stacked admission at 4 set shards: STILL one device dispatch per
    # batch (round-grid shard_map over the ("sets",) mesh; collapsed to
    # the single donated scan on a 1-device host).
    st_batches = iter(np.split(all_fps + 4_000_000, n_batches))
    idx_st = MonarchKVIndex(KVIndexConfig(
        n_sets=8, n_shards=4, admit_after_reads=0))
    t = time_callable(lambda: idx_st.admit_fps(next(st_batches)),
                      warmup=2, reps=reps)
    timings["kv_index_admit_stacked"] = t
    assert idx_st.stats.admit_calls == reps + 2   # ONE dispatch per batch
    print(f"kv_index admit 64 fps, 4 set shards (stacked): "
          f"{t.median_us:.0f} us ({idx_st.stats.admit_calls} dispatches/"
          f"{reps + 2} batches)")
    csv_rows.append(f"kv_index_admit_stacked,{t.median_us:.0f},4shards")

    # device-resident hopscotch insert (apps/hashtable.py device backend):
    # one donated device call per insert — windowed scatter + bounded
    # hop-chain while-loop — vs the numpy reference store.  32 inserts
    # per timed call; fresh keys every call, sized so no rehash occurs.
    from repro.apps.hashtable import HopscotchTable
    ins_per_call = 32
    ht_keys = iter(range(1, 1 + ins_per_call * (reps + 2) * 2))
    ht_dev = HopscotchTable(12, window=32, backend="device")

    def _insert_many(table):
        for _ in range(ins_per_call):
            table.insert(next(ht_keys), 7)

    t = time_callable(lambda: _insert_many(ht_dev), warmup=1, reps=reps)
    timings["hashtable_insert_device"] = t
    print(f"hashtable insert x{ins_per_call} (device backend): "
          f"{t.median_us:.0f} us ({t.median_us / ins_per_call:.1f} "
          f"us/insert)")
    csv_rows.append(f"hashtable_insert_device,{t.median_us:.0f},"
                    f"{ins_per_call}ins")

    ht_host = HopscotchTable(12, window=32, backend="host")
    t2 = time_callable(lambda: _insert_many(ht_host), warmup=1, reps=reps)
    timings["hashtable_insert_host"] = t2
    print(f"hashtable insert x{ins_per_call} (host reference): "
          f"{t2.median_us:.0f} us")
    csv_rows.append(f"hashtable_insert_host,{t2.median_us:.0f},"
                    f"{ins_per_call}ins")

    # async admission: a serving-loop step is admit(64 fps) + model
    # compute.  Inline pays admit + compute in series; behind the
    # AdmitQueue the worker drains the install WHILE the jitted compute
    # runs (XLA releases the GIL), so a window of steps should approach
    # max(sum compute, sum admit) — the admit latency is hidden.  Each
    # timed callable is a WHOLE window of steps plus (async) the drain
    # barrier: throughput, not per-step latency, because a single step's
    # cost depends on where the worker happens to be, which made the
    # per-step median a coin flip on a contended CPU.  Fresh unique
    # fingerprints every step, as in the batched-admit bench above.
    win_steps = 6
    w_proxy = jnp.asarray(rng.standard_normal((256, 256)).astype(np.float32))

    @jax.jit
    def _compute_proxy(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    n_windows = reps + 1               # warmup=1
    n_async = n_fp * win_steps * n_windows * 2
    async_fps = 1 + np.arange(n_async, dtype=np.uint32) + 2_000_000
    half = n_async // 2
    inline_iter = iter(np.split(async_fps[:half], half // n_fp))
    queue_iter = iter(np.split(async_fps[half:], half // n_fp))

    idx_in = MonarchKVIndex(KVIndexConfig(n_sets=8, admit_after_reads=0))

    def window_inline():
        for _ in range(win_steps):
            idx_in.admit_fps(next(inline_iter))
            jax.block_until_ready(_compute_proxy(w_proxy))

    t_in = time_callable(window_inline, warmup=1, reps=reps)
    timings["kv_index_admit_inline"] = t_in

    idx_as = MonarchKVIndex(KVIndexConfig(n_sets=8, admit_after_reads=0))
    q = AdmitQueue(idx_as, background=True, read_your_writes=False)

    def window_async():
        for _ in range(win_steps):
            q.submit(next(queue_iter))
            jax.block_until_ready(_compute_proxy(w_proxy))
        q.flush()                      # window completes all its installs

    t_as = time_callable(window_async, warmup=1, reps=reps)
    q.close()
    timings["kv_index_admit_async"] = t_as
    hidden = (t_in.median_us - t_as.median_us) / win_steps
    print(f"kv_index admit 64 fps + compute x{win_steps}: "
          f"inline {t_in.median_us:.0f} us vs async {t_as.median_us:.0f} us"
          f" incl. drain ({hidden:.0f} us/step of admit latency hidden)")
    csv_rows.append(f"kv_index_admit_inline,{t_in.median_us:.0f},"
                    f"{win_steps}x64fp")
    csv_rows.append(f"kv_index_admit_async,{t_as.median_us:.0f},"
                    f"{win_steps}x64fp")

    # wear-op microbench: a 256-write trace through the donated device op
    # (the §8 accounting the admit pipeline fuses per install).
    wcfg = wear.WearConfig(n_supersets=64, m_writes=3, dc_limit=1 << 20,
                           t_mww_cycles=1 << 20)
    ss = rng.integers(0, 64, 256).astype(np.int32)
    dirty = rng.integers(0, 2, 256).astype(bool)
    cycles = np.arange(256, dtype=np.int32)
    wstate_box = [wear.init_state(wcfg)]

    def _wear_call():
        st, _, _ = wear.record_writes_device(
            wstate_box[0], wcfg, ss, dirty, cycles)
        wstate_box[0] = st
        return st.write_counter

    t = time_callable(_wear_call, warmup=2, reps=reps)
    timings["wear_record_batch"] = t
    print(f"wear record_writes 256-write trace: {t.median_us:.0f} us "
          f"({t.median_us / 256:.2f} us/write)")
    csv_rows.append(f"wear_record_batch,{t.median_us:.0f},256w")

    # roofline check: analytic HBM traffic per launch for the search
    # kernels (every operand + result touched once — the same byte terms
    # roofline/analysis.py uses), turned into achieved bytes/s at the
    # measured median and a fraction of the active machine's bandwidth
    # ceiling.  On this interpret-mode rig the fractions are tiny (the
    # interpreter, not the memory system, is the wall) — what the numbers
    # pin is the 8x plane-traffic drop from packing, which survives any
    # machine profile.
    from repro.roofline.analysis import current_machine
    machine = current_machine()
    q_ms, out_b = 128, 128 * 4
    kernel_bytes = {
        "xam_search": 64 * 64 * 2 + 64 * 512 + 64 * 4,
        "xam_multiset": q_ms * r * 2 + n_sets * (r * c + c) + out_b,
        "xam_multiset_packed":
            q_ms * r * 2 + n_sets * ((r // 8) * c + c) + out_b,
    }
    roofline = {"machine": machine.name, "hbm_bw": machine.hbm_bw,
                "kernels": {}}
    for name, nbytes in kernel_bytes.items():
        med_s = timings[name].median_us * 1e-6
        achieved = nbytes / med_s if med_s > 0 else 0.0
        frac = achieved / machine.hbm_bw
        roofline["kernels"][name] = {
            "hbm_bytes": nbytes,
            "achieved_bytes_per_s": round(achieved, 1),
            "roofline_fraction": frac,
        }
        print(f"roofline {name}: {nbytes} B/launch, "
              f"{achieved / 1e6:.1f} MB/s achieved "
              f"({frac:.2e} of {machine.name} HBM bw)")

    emit_json("kernels", {
        "reps": reps,
        "timings_us": {
            name: {"median": t.median_us, "best": t.best_us,
                   "mean": t.mean_us}
            for name, t in timings.items()},
        "kv_index_hit_rate": float(idx.hit_rate),
        "roofline": roofline,
    }, quick=quick)
