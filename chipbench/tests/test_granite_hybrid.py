"""The granite-4.0-h-micro hybrid's benchmark files: its reference's
counts pinned at the cell's shapes, the reference against the Mamba-2
recurrence and the stated equations written out by hand in float64,
the ``state_resume_share`` reader on recorded answers, and a whole run
of the reduced hybrid on the CPU (the look for a chip skipped), sound
and with its restored slabs or state snapshots broken."""
import json
import math
import pathlib
import types

import numpy as np
import pytest

import jax

from chipbench import cost, harness, reference, traffic
from chipbench.reference import granite_hybrid

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = pathlib.Path(__file__).parent / "tiny"
CFG = harness.load_config(ROOT / "chipbench/configs/granite-4.0-h-micro.json")

#: At the granite-h-micro.docqa-4k shapes, rows 1-8: a full 4224-token
#: prefill, a 128-token prefill after a restored 4096-token prefix, and
#: decode from position 4224 to 4255 (the exact sum over positions).
PINNED = {
    "prefill_flops": {
        (0, 4224): [25912077713408.0, 51824155426816.0, 77736233140224.0,
                    103648310853632.0, 129560388567040.0, 155472466280448.0,
                    181384543993856.0, 207296621707264.0],
        (4096, 128): [794202996736.0, 1588405993472.0, 2382608990208.0,
                      3176811986944.0, 3971014983680.0, 4765217980416.0,
                      5559420977152.0, 6353623973888.0]},
    "decode_flops": {
        (4224, 4255): [211685212160.0, 423370424320.0, 635055636480.0,
                       846740848640.0, 1058426060800.0, 1270111272960.0,
                       1481796485120.0, 1693481697280.0]},
    "decode_bytes": {
        (4224, 4255): [210222841856.0, 216226856960.0, 222230872064.0,
                       228234887168.0, 234238902272.0, 240242917376.0,
                       246246932480.0, 252250947584.0]},
}


@pytest.mark.parametrize("fn", sorted(PINNED))
def test_counts_are_pinned(fn):
    for shape, want in PINNED[fn].items():
        if fn == "prefill_flops":
            got = [cost.prefill_flops(CFG, rows, *shape)
                   for rows in range(1, 9)]
        else:
            lo, hi = shape
            got = [math.fsum(getattr(cost, fn)(CFG, rows, pos)
                             for pos in range(lo, hi + 1))
                   for rows in range(1, 9)]
        assert got == want, (shape, got)


def test_sizes_are_the_published_models():
    """3.19 B parameters, 8 KiB of KV a token, a 76.4 MB state a row;
    a resumed 128-token prefill counted by hand."""
    mod = reference.module(CFG)
    assert mod.weight_bytes(CFG) == 2 * 3190919168
    assert mod.kv_bytes_per_token(CFG) == 8192
    assert mod.state_bytes(CFG) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    d, f, v = 2048, 8192, 100352
    mamba = d * 4096 + d * 4352 + d * 64 + 4096 * d + 4 * 4352 + 3 * d * f
    attn = d * 2048 + 2 * d * 512 + 2048 * d + 3 * d * f
    per_token = 2 * (36 * mamba + 4 * attn) + 36 * 5 * 64 * 64 * 128
    keys = 128 * 4096 + 128 * 129 / 2
    assert cost.prefill_flops(CFG, 1, 4096, 128) == (
        per_token * 128 + 4 * 4 * 32 * 64 * keys + 2 * d * v)


#: A tiny hybrid in the granite configuration's keys: two Mamba-2 layers
#: around an attention layer.
TINY_HYBRID = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 97, "rms_norm_eps": 1e-5,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_d_state": 8,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_conv": 4,
    "embedding_multiplier": 12.0, "attention_multiplier": 0.125,
    "residual_multiplier": 0.22, "logits_scaling": 8.0,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "init": {"seed": 5, "group_layers": 3, "embedding_std": 0.02,
             "dt_range": [0.001, 0.1]}}


def _silu(v):
    return v / (1 + np.exp(-v))


def _norm(v, eps=1e-5):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps)


def _hand_mamba(w, vec, h, cfg):
    """One Mamba-2 mixer by hand in float64, token by token: conv taps,
    then per head state <- exp(dt A) state + dt x B^T, y = state C + D x,
    then the gated norm and the out-projection."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    vec = {k: np.asarray(v, np.float64) for k, v in vec.items()}
    h = np.asarray(h, np.float64)
    nh, p, n, k = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"], cfg["mamba_d_conv"])
    di = nh * p
    b, s, _ = h.shape
    z, xbc, dtr = h @ w["wz"], h @ w["wxbc"], h @ w["wdt"]
    out = np.zeros((b, s, di))
    for r in range(b):
        state = np.zeros((nh, p, n))
        for t in range(s):
            conv = sum(w["conv_w"][i] * xbc[r, t - (k - 1) + i]
                       for i in range(k) if t - (k - 1) + i >= 0)
            u = _silu(conv + vec["conv_b"])
            x, bt, ct = u[:di].reshape(nh, p), u[di:di + n], u[di + n:]
            dt = np.log1p(np.exp(dtr[r, t] + vec["dt_bias"]))
            a = -np.exp(vec["a_log"])
            state = (np.exp(dt * a)[:, None, None] * state
                     + (dt[:, None] * x)[..., None] * bt[None, None, :])
            out[r, t] = (state @ ct + vec["d_skip"][:, None] * x).reshape(di)
    return _norm(out * _silu(z), cfg["rms_norm_eps"]) @ w["wout"]


def test_reference_mamba_is_the_recurrence():
    """The reference's Mamba-2 mixer against the recurrence written out
    by hand in float64: they differ by float32 rounding alone (1e-5 of
    the largest output)."""
    cfg = TINY_HYBRID
    _, keys = granite_hybrid.model_keys(cfg)
    w = granite_hybrid.layer_weights(keys[0], cfg, "mamba")
    h = np.random.default_rng(2).normal(size=(2, 11, 32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = granite_hybrid._mamba(w, jax.numpy.asarray(h), cfg,
                                    granite_hybrid._dims(cfg))
    want = _hand_mamba(w, granite_hybrid.mamba_vectors(cfg), h, cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_reference_follows_the_stated_equations():
    """The whole reference at a tiny size against the configuration's
    equations written out in float64 with the hand mixer: embedding x
    12, residual branches x 0.22, NoPE causal GQA at the attention
    multiplier, tied head over 8."""
    cfg = TINY_HYBRID
    toks = np.random.default_rng(3).integers(1, 97, (2, 9))
    k_embed, keys = granite_hybrid.model_keys(cfg)
    emb = np.asarray(granite_hybrid.embed_weights(k_embed, cfg), np.float64)
    vec = granite_hybrid.mamba_vectors(cfg)
    x = emb[toks] * 12
    for key, kind in zip(keys, cfg["layer_types"]):
        w = granite_hybrid.layer_weights(key, cfg, kind)
        w64 = {k: np.asarray(v, np.float64) for k, v in w.items()}
        h = _norm(x)
        if kind == "mamba":
            mix = _hand_mamba(w, vec, h, cfg)
        else:
            q = (h @ w64["wq"]).reshape(2, 9, 4, 8)
            k = np.repeat((h @ w64["wk"]).reshape(2, 9, 2, 8), 2, axis=2)
            v = np.repeat((h @ w64["wv"]).reshape(2, 9, 2, 8), 2, axis=2)
            sc = np.einsum("bqhd,bkhd->bhqk", q, k) * 0.125
            sc = np.where(np.tril(np.ones((9, 9), bool)), sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            mix = (np.einsum("bhqk,bkhd->bqhd", pr, v).reshape(2, 9, 32)
                   @ w64["wo"])
        x = x + 0.22 * mix
        h = _norm(x)
        x = x + 0.22 * ((_silu(h @ w64["w_gate"]) * (h @ w64["w_up"]))
                        @ w64["w_down"])
    want = _norm(x[:, 4:]) @ emb.T / 8
    got = granite_hybrid.logits(cfg, toks, 4)
    # float32 against float64 through three layers and a head whose sums
    # cancel (logits of about 0.01 from terms of 0.02): it reads 1.5e-4
    # of the largest logit; the bound is 1e-3 (a wrong multiplier, mask
    # or norm moves them by tens of percent).
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-3 * np.abs(want).max())


def test_state_resume_share_reads_the_answers():
    """Resumed over hit chunks of the answered requests: a hit run cut
    back to its snapshot shows as hit chunks computed again."""
    def rec(status, doc):
        return types.SimpleNamespace(status=status, doc=doc)
    answers = [rec(200, {"chunks": 264, "hit_chunks": 256,
                         "resumed_chunks": 256}),
               rec(200, {"chunks": 264, "hit_chunks": 263,
                         "resumed_chunks": 256}),
               rec(200, {"chunks": 264, "hit_chunks": 0,
                         "resumed_chunks": 0}),
               rec(429, None)]
    read = harness._reader("state_resume_share.online")
    got = read(types.SimpleNamespace(window=answers))
    assert got == pytest.approx(100 * 512 / 519)
    assert read(types.SimpleNamespace(window=answers[2:])) is None


# ---------------------------------------------------------------------------
# A whole run of the reduced hybrid.
# ---------------------------------------------------------------------------

def _cell():
    """The reduced granite hybrid (the published 256-token SSD chunk) on
    256-token documents: each prompt snapshots its state at token 256.
    Its logits are small (a spread of about 0.003: tied embeddings drawn
    at 0.002, over 8), and so is its limit: at this seed the sound run
    reads a gap of 0.00014, zeroed slabs and zeroed state snapshots alone
    0.0010."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny-hybrid", spec={"name": "tiny-hybrid", "chips": 1},
        cfg=harness.load_config(TINY / "tiny-granite.json"),
        mix=traffic.load(TINY / "tiny-docs-256.json"),
        limits=json.loads((TINY / "tiny-granite-limits.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"]
                    if m["name"] in ("req_p50_ms", "setup_s")],
        per_layer=[m for m in bench["per_layer"]
                   if m["name"] in ("resumed_share.online",
                                    "state_resume_share.online")])


def _run(tamper=None):
    return harness.run(_cell(), seed=2 ** 33 + 17, seconds=2.0, trace=False,
                       require_chip=False, tamper=tamper)


def _zero_slabs(frontend, admit_q):
    """Restored slabs, KV and state, come back as zeros."""
    store = admit_q.index.slab_store
    get = store.get

    def broken(fp):
        slab = get(fp)
        return None if slab is None else jax.tree.map(np.zeros_like, slab)
    store.get = broken


def _zero_states(frontend, admit_q):
    """Restored state snapshots come back as zeros (the KV intact)."""
    store = admit_q.index.slab_store
    get = store.get

    def broken(fp):
        slab = get(fp)
        if slab is None or slab["state"] is None:
            return slab
        return {"kv": slab["kv"],
                "state": jax.tree.map(np.zeros_like, slab["state"])}
    store.get = broken


def test_sound_hybrid_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0


def test_hybrid_requests_resume_from_their_snapshots():
    """After the fill, a request of the window resumes its document's 16
    chunks: the KV slabs and the snapshot at token 256."""
    cell = _cell()
    plan = traffic.build(cell.mix, cell.cfg["vocab_size"], 2 ** 33 + 17, 2.0)
    frontend, admit_q = harness.boot(cell.cfg, cell.mix)
    try:
        harness.fill(frontend, admit_q, plan)
        out = frontend.router.submit(plan.tokens(0), timeout=600.0)
    finally:
        frontend.shutdown()
        admit_q.close()
    assert out["hit_chunks"] == 16 and out["resumed_chunks"] == 16


@pytest.mark.parametrize("fault", [_zero_slabs, _zero_states])
def test_hybrid_fault_is_caught(fault):
    r = _run(fault)
    assert not r["correct"], r["checks"]
