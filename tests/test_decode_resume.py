"""Prefix-cache decode resume: the token-identity tier.

The tentpole pin: greedy decode from a RESUMED cached prefix equals the
full-prefill decode token-for-token — the prefix cache must be a pure
compute optimization, never a numerics change.  Pinned here at three
levels:

* ``transformer.prefill(prefix_kv=...)`` directly: logits, the whole
  decode-cache pytree, AND the returned suffix KV match a full prefill
  to bf16 rounding, swept over RoPE on/off (``ArchConfig.use_rope``),
  attention kinds (all-global yi-9b, local+global gemma3), and prompt
  lengths straddling ``CHUNK_TOKENS`` boundaries.
* :class:`repro.serve.resume.PrefixResumeEngine` through the index +
  slab store: hits restore slabs, misses recompute, rotation keeps hits
  (slab keys are fingerprints — rotation remaps sets, evicts nothing),
  eviction drops the slab and degrades to a full recompute, a
  hit-without-slab truncates the resume run — in every case the decoded
  tokens match the no-cache reference.
* The full serving loop (``run_request_loop`` + ``AdmitQueue``): a
  randomized zipf schedule replayed at ``n_shards in {1, 2, 4}`` and
  through the sequential reference index (``tests/kv_index_reference.py``)
  produces identical per-request hits/resumed counts, identical policy
  state (installs, planes, wear), and identical decoded tokens.  Rides
  the CI forced-4-device leg, where the shard counts get real placement.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.serve import run_request_loop
from repro.models import transformer
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import (CHUNK_TOKENS, KVIndexConfig, KVSlabStore,
                                  MonarchKVIndex)
from repro.serve.resume import PrefixResumeEngine
from kv_index_reference import ReferenceKVIndex

SHARD_COUNTS = (1, 2, 4)


def _arch(kind: str, use_rope: bool = True):
    """CI-sized archs by attention mix: all-global, all-local, or both."""
    if kind == "global":
        cfg = configs.get_arch("yi-9b").reduced()
    elif kind == "local":
        cfg = configs.get_arch("gemma3-27b").reduced()
    else:                                  # 5 local + 1 global (5:1 pattern)
        cfg = dataclasses.replace(
            configs.get_arch("gemma3-27b").reduced(), n_layers=6)
    return dataclasses.replace(cfg, use_rope=use_rope)


def _greedy(params, cfg, logits, cache, pos, n=3):
    """(B, n) greedy tokens, and each row's smallest top-2 logit margin
    over the n steps."""
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    outs, margins = [], []
    for t in range(n):
        outs.append(np.asarray(nxt))
        top2 = np.sort(np.asarray(logits, np.float32), axis=-1)[:, -2:]
        margins.append(top2[:, 1] - top2[:, 0])
        logits, cache = transformer.decode_step(
            params, cfg, nxt, cache, jnp.int32(pos + t))
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    return np.concatenate(outs, axis=1), np.min(margins, axis=0)


# Resumed vs full prefill agree to bf16 rounding, not bit for bit: the
# resumed prefill multiplies only the suffix, so XLA may tile its matmuls
# and order the bf16 accumulation differently than for the whole prompt
# (it does on some backends and device counts), and a rounding that flips
# in one layer is carried into every later one.  A bf16 ulp is 2**-7 of a
# value's magnitude; the bound is four ulps of the tensor's largest entry.
BF16_TOL = 2.0 ** -5


def _tol(ref) -> float:
    return BF16_TOL * float(np.max(np.abs(np.asarray(ref, np.float32))))


def _arrays_match(a, b, msg):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=0, atol=_tol(b), err_msg=msg)


def _tree_match(a, b, msg):
    for pa, (path, la) in zip(jax.tree.leaves(b),
                              jax.tree_util.tree_leaves_with_path(a)):
        _arrays_match(la, pa, (msg, path))


# ---------------------------------------------------------------------------
# Transformer-level resume identity.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_rope", [True, False])
@pytest.mark.parametrize("kind", ["global", "local", "mixed"])
def test_resume_prefill_bit_identity(rng, kind, use_rope):
    """Resume-from-offset prefill == full prefill to bf16 rounding
    (``BF16_TOL``): logits, every decode cache leaf, the suffix KV, and 3
    greedy decode tokens for every row whose top-2 logit margins clear
    the logit tolerance twice over (both sides may move by it), for
    prefix lengths straddling chunk boundaries (S=40 leaves a 8-token
    partial chunk that is always recomputed)."""
    cfg = _arch(kind, use_rope)
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    for s, p_chunks in ((48, 1), (48, 2), (40, 1)):
        toks = rng.integers(1, cfg.vocab_size, (2, s)).astype(np.int32)
        max_seq = s + 4
        lg_f, cache_f, kv_f = transformer.prefill(
            params, cfg, {"tokens": toks}, max_seq, return_kv=True)
        p_len = p_chunks * CHUNK_TOKENS
        prefix_kv = jax.tree.map(lambda a: a[..., :p_len, :, :], kv_f)
        lg_r, cache_r, kv_r = transformer.prefill(
            params, cfg, {"tokens": toks[:, p_len:]}, max_seq,
            prefix_kv=prefix_kv, return_kv=True)
        tag = f"{kind} rope={use_rope} S={s} P={p_len}"
        _arrays_match(lg_r, lg_f, tag)
        _tree_match(cache_r, cache_f, tag)
        _tree_match(kv_r, jax.tree.map(lambda a: a[..., p_len:, :, :], kv_f),
                    tag)
        want, margin = _greedy(params, cfg, lg_f, cache_f, s)
        got, _ = _greedy(params, cfg, lg_r, cache_r, s)
        clear = margin > 2 * _tol(lg_f)
        np.testing.assert_array_equal(got[clear], want[clear], err_msg=tag)


def test_resume_rejects_recurrent_arch():
    """SSM state folds the whole prefix into one vector — the resume
    path must refuse, not silently corrupt."""
    ssm = configs.get_arch("falcon-mamba-7b").reduced()
    assert not transformer.resume_supported(ssm)
    with pytest.raises(NotImplementedError):
        transformer.prefill({}, ssm, {"tokens": np.zeros((1, 32), np.int32)},
                            40, prefix_kv={"dummy": np.zeros((1, 16, 1, 1))})
    idx = MonarchKVIndex(KVIndexConfig(fingerprint="prefix"),
                         slab_store=KVSlabStore())
    with pytest.raises(NotImplementedError):
        PrefixResumeEngine({}, ssm, max_seq=40, index=idx)


def test_engine_requires_prefix_fingerprints_and_store():
    cfg = _arch("global")
    with pytest.raises(ValueError, match="fingerprint"):
        PrefixResumeEngine({}, cfg, max_seq=64,
                           index=MonarchKVIndex(KVIndexConfig(),
                                                slab_store=KVSlabStore()))
    with pytest.raises(ValueError, match="KVSlabStore"):
        PrefixResumeEngine({}, cfg, max_seq=64, index=MonarchKVIndex(
            KVIndexConfig(fingerprint="prefix")))


# ---------------------------------------------------------------------------
# Engine + index + slab store.
# ---------------------------------------------------------------------------

def _mk_index(n_shards=1, **kw):
    base = dict(n_sets=8, set_ways=8, admit_after_reads=0,
                rotate_every=1 << 30, fingerprint="prefix")
    base.update(kw)
    return MonarchKVIndex(KVIndexConfig(n_shards=n_shards, **base),
                          slab_store=KVSlabStore())


def _mk_engine(idx, cfg=None, max_seq=80, seed=1):
    cfg = cfg or _arch("global")
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    return PrefixResumeEngine(params, cfg, max_seq=max_seq, index=idx,
                              decode_tokens=4, jit=False)


def _serve_once(engine, q, toks):
    """One request through the production flow; returns (record-ish,
    decoded)."""
    hits = q.lookup(toks)
    res = engine.prefill(toks, hits)
    q.submit_tokens(toks, slabs=res.slabs)
    return res, engine.decode(res)


def test_engine_hit_resumes_and_decodes_identically(rng):
    """First serving computes + admits; the second serving of the same
    prompt resumes all but the final chunk and decodes the same tokens.
    A fresh no-cache engine double-checks the reference."""
    idx = _mk_index()
    engine = _mk_engine(idx)
    q = AdmitQueue(idx)
    try:
        toks = rng.integers(1, 512, (1, 64)).astype(np.int32)
        res1, dec1 = _serve_once(engine, q, toks)
        assert res1.resumed_chunks == 0 and res1.computed_chunks == 4
        res2, dec2 = _serve_once(engine, q, toks)
        assert res2.resumed_chunks == 3          # run capped at n_chunks-1
        np.testing.assert_array_equal(dec1, dec2)
        # straddling prompt: 4 chunks + 8 leftover tokens, same story
        odd = rng.integers(1, 512, (1, 72)).astype(np.int32)
        r1, d1 = _serve_once(engine, q, odd)
        r2, d2 = _serve_once(engine, q, odd)
        assert r2.resumed_chunks == 4 and r2.computed_chunks == 0
        np.testing.assert_array_equal(d1, d2)
        audit = idx.slab_lockstep_report()
        assert not audit["missing_slabs"] and not audit["orphan_slabs"]
    finally:
        q.close()


def test_engine_hit_survives_rotation(rng):
    """Rotation remaps sets but evicts nothing: the hit AND its slabs
    survive, and the resumed decode still matches."""
    idx = _mk_index()
    engine = _mk_engine(idx)
    q = AdmitQueue(idx)
    try:
        toks = rng.integers(1, 512, (1, 64)).astype(np.int32)
        _, dec_ref = _serve_once(engine, q, toks)
        q.rotate()
        assert idx.stats.rotations == 1
        res, dec = _serve_once(engine, q, toks)
        assert res.resumed_chunks == 3
        np.testing.assert_array_equal(dec_ref, dec)
        audit = idx.slab_lockstep_report()
        assert not audit["missing_slabs"] and not audit["orphan_slabs"]
    finally:
        q.close()


def test_engine_eviction_drops_slab_and_recomputes(rng):
    """Pressure-evicted prefix: the slab store drops in lockstep, the
    next serving misses cleanly and recomputes — same decoded tokens,
    no orphan slabs."""
    idx = _mk_index(n_sets=4, set_ways=4)
    engine = _mk_engine(idx)
    q = AdmitQueue(idx)
    try:
        toks = rng.integers(1, 512, (1, 64)).astype(np.int32)
        _, dec_ref = _serve_once(engine, q, toks)
        fps0 = {int(f) for f in idx.fingerprints(toks).reshape(-1)}
        flood = rng.integers(1 << 20, 1 << 30, 4096).astype(np.uint32)
        q.submit(np.unique(flood))
        q.flush()
        assert idx.stats.evictions > 0
        evicted = fps0 - set(idx.slot_of)
        assert evicted, "flood failed to evict the prefix"
        assert all(idx.slab_store.get(f) is None for f in evicted)
        res, dec = _serve_once(engine, q, toks)
        assert res.resumed_chunks < 3
        np.testing.assert_array_equal(dec_ref, dec)
        audit = idx.slab_lockstep_report()
        assert not audit["orphan_slabs"]
    finally:
        q.close()


def test_engine_truncates_run_at_missing_slab(rng):
    """A hit whose slab is gone (admitted slab-less) truncates the
    resume run instead of serving garbage."""
    idx = _mk_index()
    engine = _mk_engine(idx)
    q = AdmitQueue(idx)
    try:
        toks = rng.integers(1, 512, (1, 64)).astype(np.int32)
        # admit WITHOUT slabs: index hits, store empty
        q.submit_tokens(toks)
        q.flush()
        assert q.lookup(toks).all()
        res, _ = _serve_once(engine, q, toks)
        assert res.resumed_chunks == 0 and res.computed_chunks == 4
        # second serving staged real slabs -> now it resumes
        res2, _ = _serve_once(engine, q, toks)
        assert res2.resumed_chunks == 3
    finally:
        q.close()


# The tiny hybrid of tests/test_hybrid_resume.py: granite-4.0-h-micro's
# layer period at reduced widths, its SSD chunk shrunk to 32.
PIPELINE_ARCHS = {
    "dense": lambda: _arch("global"),
    "hybrid": lambda: dataclasses.replace(
        configs.get_arch("granite-4.0-h-micro").reduced(), ssm_chunk=32),
}


@pytest.fixture(scope="module", params=list(PIPELINE_ARCHS))
def pipeline_engine(request):
    """A jitted engine per architecture, shared by the cases below so
    each compiles its prefill and decode step once."""
    cfg = PIPELINE_ARCHS[request.param]()
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    return PrefixResumeEngine(params, cfg, max_seq=80, index=_mk_index(),
                              decode_tokens=4)


@pytest.mark.parametrize("n", [1, 2, 5])
def test_decode_runs_one_step_ahead_and_drops_none(rng, monkeypatch,
                                                   pipeline_engine, n):
    """The decode loop dispatches step t before it reads token t: the
    tokens equal a loop that reads each token before the next step, and
    n tokens take n-1 steps (none whose result is thrown away)."""
    engine = pipeline_engine
    toks = rng.integers(1, engine.cfg.vocab_size, (2, 64)).astype(np.int32)
    res = engine.prefill(toks)
    step = engine._decode
    calls = []

    def counted(*args):
        calls.append(args[3])
        return step(*args)

    monkeypatch.setattr(engine, "_decode", counted)
    got = engine.decode(res, n)
    assert [int(p) for p in calls] == list(range(64, 64 + n - 1))

    cache, want = res.state["cache"], []
    nxt = jnp.argmax(res.state["logits"], -1).astype(jnp.int32)[:, None]
    for t in range(n):
        want.append(np.asarray(nxt))
        nxt, _, cache = step(engine.params, cache, nxt, jnp.int32(64 + t))
    assert got.shape == (2, n)
    np.testing.assert_array_equal(got, np.concatenate(want, axis=1))


# ---------------------------------------------------------------------------
# Slab residency: device slabs, host slabs and a mix restore the same bits.
# ---------------------------------------------------------------------------

def _slab_bytes(cfg) -> int:
    """Bytes of one chunk's slab (every layer's k and v)."""
    kv = jax.eval_shape(
        lambda: transformer.prefill(
            transformer.init_params(jax.random.PRNGKey(0), cfg), cfg,
            {"tokens": jnp.zeros((1, CHUNK_TOKENS), jnp.int32)},
            CHUNK_TOKENS, return_kv=True)[2])
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


def _host_restore(slabs):
    """The restore as it was done on the host: per-row runs concatenated
    with NumPy along the sequence axis, then the rows, then one upload."""
    def cat(axis_from_right, trees):
        return jax.tree.map(lambda *xs: np.concatenate(
            xs, axis=xs[0].ndim - axis_from_right), *trees)
    rows = [cat(3, [jax.tree.map(np.asarray, s) for s in row])
            for row in slabs]
    return jax.tree.map(jnp.asarray, cat(4, rows))


# Device budget of each residency, in slabs: unbounded (the CPU backend
# reports no memory stats), none, or three of the eight first computed.
RESIDENCY = {"device": None, "host": 0, "mixed": 3}


@pytest.mark.parametrize("residency", list(RESIDENCY))
@pytest.mark.parametrize("kind", ["global", "remainder"])
def test_slab_restore_bit_identity(rng, kind, residency):
    """A resumed prefill from device-resident slabs, from slabs spilled to
    host memory, and from a mix of both, through the jitted engine: its
    logits, decode cache and decoded tokens equal bit for bit those of
    the host-side restore (NumPy concatenation and one upload of the same
    slabs), and match a full prefill of the whole prompt as the
    transformer-level test does.  ``global`` is yi-9b (every layer in the
    scanned group), ``remainder`` gemma3 at 7 layers (a scanned group of
    6 plus one unscanned layer)."""
    cfg = (_arch("global") if kind == "global" else dataclasses.replace(
        configs.get_arch("gemma3-27b").reduced(), n_layers=7))
    if kind == "remainder":
        assert cfg.scan_groups()[1] == 1 and len(cfg.scan_groups()[2]) == 1
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    n = _slab_bytes(cfg)
    budget = RESIDENCY[residency]
    store = KVSlabStore(device_budget=None if budget is None
                        else budget * n)
    idx = MonarchKVIndex(KVIndexConfig(
        n_sets=8, set_ways=8, admit_after_reads=0, rotate_every=1 << 30,
        fingerprint="prefix"), slab_store=store)
    engine = PrefixResumeEngine(params, cfg, max_seq=68, index=idx,
                                decode_tokens=3)
    q = AdmitQueue(idx)
    try:
        toks = rng.integers(1, cfg.vocab_size, (2, 64)).astype(np.int32)
        first = engine.prefill(toks, q.lookup(toks))
        assert len(first.slabs) == 8
        q.submit_tokens(toks, slabs=first.slabs)
        q.flush()
        on_device = 8 if budget is None else budget
        assert store.stats() == {"resident": 8,
                                 "device_bytes": on_device * n,
                                 "host_bytes": (8 - on_device) * n,
                                 "spilled": 8 - on_device}
        res = engine.prefill(toks, q.lookup(toks))
        assert res.resumed_chunks == 2 * 3
    finally:
        q.close()
    fps = idx.fingerprints(toks)
    slabs = [store.get_many(fps[r, :3]) for r in range(2)]
    placed = {isinstance(a, jax.Array)
              for row in slabs for a in jax.tree.leaves(row)}
    assert placed == {"device": {True}, "host": {False},
                      "mixed": {True, False}}[residency]
    lg_h, cache_h, _ = engine._prefill(
        params, {"tokens": toks[:, 3 * CHUNK_TOKENS:]}, _host_restore(slabs))
    got = res.state
    np.testing.assert_array_equal(np.asarray(got["logits"]),
                                  np.asarray(lg_h))
    for a, b in zip(jax.tree.leaves(got["cache"]), jax.tree.leaves(cache_h)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    dec = engine.decode(res)
    np.testing.assert_array_equal(
        dec, engine.decode({"logits": lg_h, "cache": cache_h, "pos": 64}))
    lg_f, cache_f, _ = engine._prefill(params, {"tokens": toks})
    _arrays_match(got["logits"], lg_f, residency)
    _tree_match(got["cache"], cache_f, residency)
    _, margin = _greedy(params, cfg, lg_f, cache_f, 64)
    want = engine.decode({"logits": lg_f, "cache": cache_f, "pos": 64})
    clear = margin > 2 * _tol(lg_f)
    np.testing.assert_array_equal(dec[clear], want[clear])


# ---------------------------------------------------------------------------
# Schedule replay: shard counts + the reference index stay in lockstep.
# ---------------------------------------------------------------------------

def _policy_state(idx):
    return dict(
        slot_of=dict(idx.slot_of),
        valid=np.asarray(idx.valid).copy(),
        fp_of=np.asarray(idx.fp_of).copy(),
        writes=idx.write_distribution(),
        window_writes=np.asarray(idx.wear_state.window_writes).copy(),
        slabs=sorted(idx.slab_store.resident_fps()),
        stats=(idx.stats.admissions, idx.stats.admission_skips,
               idx.stats.evictions, idx.stats.chunk_hits,
               idx.stats.chunk_misses),
    )


def _zipf_requests(n, rng):
    """(1, 64) prompts: 2 zipf-shared prefix chunks + 2 fresh tail chunks."""
    prefixes = [rng.integers(1, 512, (1, 2 * CHUNK_TOKENS))
                for _ in range(2)]
    out = []
    for _ in range(n):
        p = prefixes[min(int(rng.zipf(1.5)) - 1, 1)]
        tail = rng.integers(1, 512, (1, 2 * CHUNK_TOKENS))
        out.append(np.concatenate([p, tail], axis=1).astype(np.int32))
    return out


def _replay(idx, requests, cfg, params):
    engine = PrefixResumeEngine(params, cfg, max_seq=72, index=idx,
                                decode_tokens=2, jit=False)
    q = AdmitQueue(idx)
    decoded = []
    _, base_decode = engine.request_fns()

    def decode_fn(toks, result):
        base_decode(toks, result)
        decoded.append(result.state["decoded"])

    try:
        recs = run_request_loop(q, requests, prefill_fn=engine.prefill,
                                decode_fn=decode_fn)
        q.flush()
    finally:
        q.close()
    return recs, decoded, idx


def test_schedule_replay_shard_lockstep(rng):
    """The ISSUE's replay pin: one randomized zipf schedule through the
    REAL loop (read-your-writes lookups, submit-after-prefill, slab
    commits off-thread) at every shard count and through the reference
    index — identical hits, resumed counts, installs/planes/wear,
    resident slabs, and decoded tokens."""
    cfg = _arch("global")
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    requests = _zipf_requests(8, rng)
    runs = {n: _replay(_mk_index(n_shards=n, admit_after_reads=1),
                       requests, cfg, params)
            for n in SHARD_COUNTS}
    ref_recs, ref_dec, ref_idx = _replay(
        ReferenceKVIndex(_mk_index(admit_after_reads=1).cfg,
                         slab_store=KVSlabStore()),
        requests, cfg, params)
    assert sum(r.hit_chunks for r in ref_recs) > 0      # schedule hits
    assert sum(r.resumed_chunks for r in ref_recs) > 0  # and resumes
    for key, (recs, dec, _idx) in runs.items():
        for a, b in zip(ref_recs, recs):
            assert (a.chunks, a.hit_chunks, a.resumed_chunks) == \
                   (b.chunks, b.hit_chunks, b.resumed_chunks), key
        for da, db in zip(ref_dec, dec):
            np.testing.assert_array_equal(da, db, err_msg=str(key))

    # Every shard count shares the reference's set geometry and admission
    # semantics -> full policy state (installs, planes, wear, resident
    # slabs) must be identical.
    ref_state = _policy_state(ref_idx)
    for key, (_recs, _dec, idx) in runs.items():
        st = _policy_state(idx)
        for k in ref_state:
            if isinstance(ref_state[k], np.ndarray):
                np.testing.assert_array_equal(ref_state[k], st[k],
                                              err_msg=f"{key}: {k}")
            else:
                assert ref_state[k] == st[k], (key, k)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
