"""granite-4.0-h-micro, a hybrid of Mamba-2 and attention layers, on the
serving path at the reduced size (its published layer period and
scalars, tiny widths) on the CPU:

* the program's prefill-then-decode logits against the plain float32
  reference (``chipbench/reference/granite_hybrid.py``, the sequential
  Mamba-2 recurrence), and the fp8 control further off;
* a prefill resumed from KV slabs plus a state snapshot, then decode,
  against a full prefill then decode;
* ``mamba2_block`` started from a mid-sequence state and conv tail
  against the uninterrupted block, to float32 rounding (a state kept in
  bfloat16 fails it);
* a ``KVSlabStore`` holding mixed KV and KV+state slabs;
* ``launch/httpd.py`` booting the hybrid with resume on, where a
  repeated prompt resumes from its snapshot;
* yi-9b's and starcoder2's logits, unchanged by the new configuration
  fields.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ATTN_GLOBAL
from repro.models import ssm, transformer
from repro.serve.admit_queue import AdmitQueue
from repro.serve.kv_index import (CHUNK_TOKENS, KVIndexConfig, KVSlabStore,
                                  MonarchKVIndex)
from repro.serve.resume import PrefixResumeEngine, snapshot_token

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARCH = "granite-4.0-h-micro"
CHUNK = 32                # the SSD chunk, shrunk from 256 for the tiny size
PREFIX, SUFFIX, DECODE = 64, 16, 6


def _reference():
    path = ROOT / "chipbench/reference/granite_hybrid.py"
    spec = importlib.util.spec_from_file_location("granite_hybrid_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(**kw):
    return dataclasses.replace(configs.get_arch(ARCH).reduced(),
                               ssm_chunk=CHUNK, **kw)


def _ref_cfg(cfg):
    """The reference's configuration dict of a program ``ArchConfig``."""
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.rms_norm_eps,
            "mamba_expand": cfg.ssm_expand, "mamba_n_groups": 1,
            "mamba_d_state": cfg.ssm_state,
            "mamba_n_heads": ssm.m2_heads(cfg),
            "mamba_d_head": cfg.ssm_head_dim, "mamba_d_conv": cfg.ssm_conv,
            "embedding_multiplier": cfg.embedding_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "num_hidden_layers": cfg.n_layers,
            "layer_types": ["attention" if k == ATTN_GLOBAL else "mamba"
                            for k in cfg.layer_pattern()],
            "init": {"seed": 0, "group_layers": cfg.attn_period,
                     "embedding_std": cfg.embed_init_std,
                     "dt_range": [0.001, 0.1]}}


@functools.lru_cache(maxsize=None)
def _steps(cfg):
    """Jitted (full prefill, decode step) of ``cfg``."""
    prefill = jax.jit(lambda p, toks, max_seq: transformer.prefill(
        p, cfg, {"tokens": toks}, max_seq), static_argnums=2)
    decode = jax.jit(lambda p, toks, cache, pos: transformer.decode_step(
        p, cfg, toks, cache, pos))
    return prefill, decode


def _served(params, cfg, logits, cache, pos, n=DECODE):
    """Greedy tokens from a prefill's logits and cache, with the logits
    each one was chosen from: (B, n) and (B, n, V)."""
    toks, seen = [], []
    for t in range(n):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        toks.append(np.asarray(nxt))
        seen.append(np.asarray(logits, np.float32))
        logits, cache = _steps(cfg)[1](params, nxt, cache, jnp.int32(pos + t))
    return np.concatenate(toks, 1), np.stack(seen, 1)


def _prompt(cfg, rows=2, s=PREFIX + SUFFIX, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (rows, s)).astype(np.int32)


def test_program_matches_the_float32_reference_and_fp8_does_not():
    cfg = _tiny()
    ref = _reference()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = _prompt(cfg)
    s = toks.shape[1]
    lg, cache = _steps(cfg)[0](params, toks, s + DECODE)
    served, got = _served(params, cfg, lg, cache, s)
    seq = np.concatenate([toks, served[:, :-1]], axis=1)
    rc = _ref_cfg(cfg)
    want = ref.logits(rc, seq, s - 1)
    ctl = ref.logits(rc, seq, s - 1, quant="fp8")
    scale = np.abs(want).max()
    program = np.abs(got - want).max() / scale
    control = np.abs(ctl - want).max() / scale
    # The program serves bfloat16 weights and activations with float32
    # accumulation and a float32 recurrent state: across 10 layers its
    # logits stay within 5% of the largest reference logit (3.0-4.1%
    # over four init seeds; bf16 rounds at 2**-9 relative, the residual
    # stream carries each layer's rounding into the next, and the
    # slowest Mamba heads sum it over the whole prompt).  Rounding the
    # weights to float8 (2**-4 relative) moves them 30-47%: at least
    # five times as far (7.4-14x over those seeds).
    assert program <= 0.05, program
    assert control > 5 * program, (control, program)


def test_resumed_prefill_then_decode_matches_full_prefill():
    """KV slabs of the prefix and the state snapshot at its end (a
    multiple of the SSD chunk) resume the suffix: logits, every cache
    leaf and the decoded tokens agree with a full prefill."""
    cfg = _tiny()
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    toks = _prompt(cfg, seed=4)
    s, max_seq = toks.shape[1], toks.shape[1] + DECODE
    assert snapshot_token(cfg, s) == PREFIX
    lg_f, cache_f, kv_f, snap = transformer.prefill(
        params, cfg, {"tokens": toks}, max_seq, return_kv=True,
        snapshot_at=PREFIX)
    prefix_kv = jax.tree.map(lambda a: a[..., :PREFIX, :, :], kv_f)
    lg_r, cache_r = transformer.prefill(
        params, cfg, {"tokens": toks[:, PREFIX:]}, max_seq,
        prefix_kv=prefix_kv, prefix_state=snap)
    # Agreement to bfloat16 rounding: the resumed call multiplies only
    # the suffix, so XLA may order a bf16 accumulation differently, and
    # a flipped rounding is carried into later layers.  Four bf16 ulps
    # (2**-7 each) of each tensor's largest entry.
    for a, b in zip(jax.tree.leaves((lg_r, cache_r)),
                    jax.tree.leaves((lg_f, cache_f))):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=0,
                                   atol=2.0 ** -5 * np.abs(b).max())
    want, seen_f = _served(params, cfg, lg_f, cache_f, s)
    got, seen_r = _served(params, cfg, lg_r, cache_r, s)
    np.testing.assert_allclose(seen_r, seen_f, rtol=0,
                               atol=2.0 ** -5 * np.abs(seen_f).max())
    top2 = np.sort(seen_f, -1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]).min(-1) > 2 ** -4 * np.abs(
        seen_f).max()
    np.testing.assert_array_equal(got[clear], want[clear])


def _recurrence(params, x, cfg):
    """The state after every token of ``x`` by the Mamba-2 recurrence in
    float64, on the inputs the block computes for its SSD (the same bf16
    projections and conv): h <- exp(dt A) h + dt x B^T."""
    di, n = ssm.d_inner(cfg), cfg.ssm_state
    xbc = jax.nn.silu(ssm._causal_depthwise_conv(
        x @ params["wxbc"], params["conv_w"], params["conv_b"]))
    xh, b, _ = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus((x @ params["wdt"]).astype(jnp.float32)
                         + params["dt_b"])
    xh, b, dt = (np.asarray(a, np.float64) for a in (xh, b, dt))
    xh = xh.reshape(*xh.shape[:2], ssm.m2_heads(cfg), cfg.ssm_head_dim)
    a = -np.exp(np.asarray(params["a_log"], np.float64))
    h = np.zeros((x.shape[0], ssm.m2_heads(cfg), cfg.ssm_head_dim, n))
    for t in range(x.shape[1]):
        h = (np.exp(dt[:, t] * a)[:, :, None, None] * h
             + (dt[:, t, :, None] * xh[:, t])[..., None]
             * b[:, t, None, None, :])
    return h


def test_mamba2_block_resumes_from_a_mid_sequence_state():
    """The block run over tokens [T, S) from the state and conv tail it
    snapshotted at T equals the uninterrupted block over [0, S): the
    outputs to bf16 rounding, the final state to float32 rounding."""
    cfg = _tiny()
    params = ssm.init_mamba2(jax.random.PRNGKey(2), cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 96, cfg.d_model))
                    * 0.5, jnp.bfloat16)
    t = 2 * CHUNK
    out, h_end, tail_end, h_t, tail_t = ssm.mamba2_block(
        params, x, cfg, return_state=True, snap_at=t)
    head, h_head, tail_head = ssm.mamba2_block(params, x[:, :t], cfg,
                                               return_state=True)
    rest, h_rest, tail_rest = ssm.mamba2_block(
        params, x[:, t:], cfg, h0=h_t, conv0=tail_t, return_state=True)
    assert h_t.dtype == h_end.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(tail_t), np.asarray(tail_head))
    np.testing.assert_array_equal(np.asarray(tail_rest),
                                  np.asarray(tail_end))
    # The snapshot is the state the uninterrupted block reaches, and the
    # resumed block carries it on, to float32 rounding: 1e-5 of the
    # state's largest entry.
    for a, b in ((h_t, h_head), (h_rest, h_end)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=1e-5 * np.abs(b).max())
    # Both are the recurrence's state: the chunked SSD only reorders
    # float32 sums (it reads 3.8e-7 of the largest entry); a state
    # rounded to bfloat16 at each chunk boundary reads 2.0e-3, ten
    # times past this bound.
    want = _recurrence(params, x, cfg)
    np.testing.assert_allclose(np.asarray(h_end), want, rtol=0,
                               atol=2e-4 * np.abs(want).max())
    out = np.asarray(out, np.float32)
    np.testing.assert_allclose(np.asarray(rest, np.float32), out[:, t:],
                               rtol=0, atol=2.0 ** -6 * np.abs(out).max())
    np.testing.assert_allclose(np.asarray(head, np.float32), out[:, :t],
                               rtol=0, atol=2.0 ** -6 * np.abs(out).max())


def _device_size(tree):
    return sum(a.on_device_size_in_bytes() for a in jax.tree.leaves(tree))


def test_slab_store_holds_kv_and_state_slabs_together():
    """A state snapshot rides in its chunk's slab: the store's device
    bytes and budget count it, and dropping the fingerprint (the index's
    eviction) frees the KV and the state together."""
    cfg = _tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = _prompt(cfg, rows=1)
    _, _, kv, snap = transformer.prefill(
        params, cfg, {"tokens": toks}, toks.shape[1], return_kv=True,
        snapshot_at=PREFIX)
    chunk = lambda c: jax.tree.map(
        lambda a: a[..., c * CHUNK_TOKENS:(c + 1) * CHUNK_TOKENS, :, :], kv)
    plain = {"kv": chunk(0), "state": None}
    held = {"kv": chunk(PREFIX // CHUNK_TOKENS - 1), "state": snap}
    kv_bytes, state_bytes = _device_size(plain), _device_size(snap)
    assert state_bytes > kv_bytes > 0

    store = KVSlabStore(device_budget=10 * kv_bytes + state_bytes)
    store.stage(1, plain)
    store.stage(2, held)
    store.commit(1)
    store.commit(2)
    assert store.device_bytes == 2 * kv_bytes + state_bytes
    assert store.get(2)["state"] is snap
    store.drop(2)
    assert store.get(2) is None
    assert store.device_bytes == kv_bytes and store.host_bytes == 0

    # A budget that fits the KV slab but not the state: the state-bearing
    # slab spills to host memory, whole, and stays servable.
    tight = KVSlabStore(device_budget=kv_bytes + state_bytes // 2)
    tight.stage(1, plain)
    tight.stage(2, held)
    tight.commit(1)
    tight.commit(2)
    assert tight.spilled == 1 and tight.device_bytes == kv_bytes
    spilled = tight.get(2)
    assert isinstance(jax.tree.leaves(spilled["state"])[0], np.ndarray)
    tight.drop(2)
    assert tight.host_bytes == 0 and tight.get(2) is None


def test_engine_resumes_from_the_snapshot_and_evicts_it_with_the_kv():
    """Through the index and the store: a repeated prefix resumes from
    its KV slabs and snapshot, token-identical to a full prefill; once
    the snapshot's slab is evicted the run finds no snapshot and the
    request takes a full prefill (a miss), never a wrong state."""
    cfg = _tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    idx = MonarchKVIndex(KVIndexConfig(n_sets=4, set_ways=32,
                                       admit_after_reads=0,
                                       fingerprint="prefix"),
                         slab_store=KVSlabStore())
    q = AdmitQueue(idx, background=False)
    s = PREFIX + SUFFIX
    eng = PrefixResumeEngine(params, cfg, max_seq=s + DECODE, index=idx,
                             decode_tokens=DECODE)
    rng = np.random.default_rng(6)
    doc = rng.integers(1, cfg.vocab_size, (1, PREFIX))

    def ask():
        toks = np.concatenate(
            [np.repeat(doc, 2, 0), rng.integers(1, cfg.vocab_size,
                                                (2, SUFFIX))],
            axis=1).astype(np.int32)
        res = eng.prefill(toks, q.lookup(toks))
        q.submit_tokens(toks, slabs=res.slabs)
        lg, cache = _steps(cfg)[0](params, toks, s + DECODE)
        want, _ = _served(params, cfg, lg, cache, s)
        return res, eng.decode(res), want

    res, got, want = ask()
    assert res.resumed_chunks == 0
    assert eng.stats()["snapshots_staged"] == 1
    assert eng.stats()["snapshots_resident"] == 1
    res, got, want = ask()
    assert res.resumed_chunks == 2 * PREFIX // CHUNK_TOKENS
    np.testing.assert_array_equal(got, want)

    snap_fp = int(idx.fingerprints(np.asarray(doc, np.int32))[
        0, PREFIX // CHUNK_TOKENS - 1])
    idx.slab_store.drop(snap_fp)
    assert eng.stats()["snapshots_resident"] == 0
    res, got, want = ask()
    assert res.resumed_chunks == 0 and eng.stats()["snapshot_misses"] == 2
    np.testing.assert_array_equal(got, want)
    q.close()


def test_recurrent_patterns_without_a_snapshot_stay_rejected():
    assert transformer.resume_supported(configs.get_arch(ARCH))
    for arch, why in (("falcon-mamba-7b", "Mamba-1"),
                      ("zamba2-2.7b", "shared attention")):
        cfg = configs.get_arch(arch).reduced()
        assert why in transformer.resume_blocker(cfg)
        idx = MonarchKVIndex(KVIndexConfig(fingerprint="prefix"),
                             slab_store=KVSlabStore())
        with pytest.raises(NotImplementedError, match=why):
            PrefixResumeEngine({}, cfg, max_seq=40, index=idx)


def _get(fe, path):
    host, port = fe.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=120) as r:
        return json.loads(r.read())


def _post(fe, toks):
    host, port = fe.address
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/generate",
        data=json.dumps({"tokens": toks.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def test_httpd_serves_the_hybrid_with_resume_on():
    """The launcher's own stack at the reduced size and the published
    256-token SSD chunk: a 288-token prompt snapshots at token 256, and
    its repeat resumes 16 chunks (its hit run of 17, cut back to the
    snapshot) and decodes the same tokens."""
    from repro.launch import httpd
    args = httpd.build_parser().parse_args(
        ["--arch", ARCH, "--reduced", "--port", "0", "--prompt-len", "288",
         "--decode-tokens", "3", "--batch-window-ms", "0",
         "--n-workers", "1", "--admit-after-reads", "0"])
    fe, q = httpd.build_frontend(args)
    assert fe.router.resume_stats is not None       # resume is on
    fe.start()
    try:
        toks = (np.arange(288, dtype=np.int32).reshape(1, 288) * 7) % 500 + 1
        first = _post(fe, toks)
        assert first["hit_chunks"] == 0 and first["resumed_chunks"] == 0
        second = _post(fe, toks)
        assert second["hit_chunks"] == 18
        assert second["resumed_chunks"] == 16
        assert second["tokens"] == first["tokens"]
        stats = _get(fe, "/stats")["resume"]
        assert stats["snapshots_staged"] == 1
        assert stats["snapshots_resident"] == 1
        assert stats["snapshot_bytes"] > 0
        assert stats["snapshot_misses"] == 1
    finally:
        fe.shutdown()
        q.close()


#: Logits of the reduced yi-9b and starcoder2 before the hybrid's
#: configuration fields existed: prefill of tokens (37 i + 11) mod
#: (vocab - 1) + 1 over 32 positions (row 0, first four entries, and the
#: sum of every |logit|), then one decode step (row 1).
PINNED = {
    "yi-9b": ([-0.981913149356842, 0.33177095651626587, -2.000608444213867,
               -0.6256933808326721], 857.4041855707765,
              [3.0131936073303223, 0.7771345376968384, 1.6566424369812012,
               0.5160641670227051], 833.95800896734),
    "starcoder2-15b": ([0.8107057809829712, -0.632239580154419,
                        -1.271651268005371, -1.6519815921783447],
                       826.9610492251813,
                       [1.377644658088684, -0.020970314741134644,
                        0.5744297504425049, 1.8377716541290283],
                       817.3504857122898),
}


def _dense_logits(cfg):
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray((np.arange(80).reshape(2, 40) * 37 + 11)
                       % (cfg.vocab_size - 1) + 1, jnp.int32)
    lg, cache = transformer.prefill(params, cfg, {"tokens": toks[:, :32]}, 48)
    lg2, _ = transformer.decode_step(params, cfg, toks[:, 32:33], cache,
                                     jnp.int32(32))
    return np.asarray(lg, np.float64), np.asarray(lg2, np.float64)


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_dense_configs_compute_what_they_did(arch):
    """The new fields default to the plain transformer: the logits are
    the pinned ones, and equal those with the fields spelled out."""
    cfg = configs.get_arch(arch).reduced()
    pre, dec = _dense_logits(cfg)
    head, total, head2, total2 = PINNED[arch]
    # Pinned on the CPU; another XLA build may order a bf16 accumulation
    # differently: a bf16 ulp (2**-7) of the largest logit, ~4.
    np.testing.assert_allclose(pre[0, :4], head, rtol=0, atol=2 ** -7 * 4)
    np.testing.assert_allclose(dec[1, :4], head2, rtol=0, atol=2 ** -7 * 4)
    assert np.abs(pre).sum() == pytest.approx(total, rel=2 ** -9)
    assert np.abs(dec).sum() == pytest.approx(total2, rel=2 ** -9)
    spelled = dataclasses.replace(
        cfg, embedding_multiplier=cfg.d_model ** 0.5,
        attention_multiplier=cfg.d_head ** -0.5, residual_multiplier=1.0,
        logits_scaling=1.0, rms_norm_eps=1e-6, attn_kv_chunk=1024)
    pre2, dec2 = _dense_logits(spelled)
    np.testing.assert_array_equal(pre2, pre)
    np.testing.assert_array_equal(dec2, dec)
