"""The float32 reference against the program at a tiny size on the CPU:
prefill (full and resumed from restored KV slabs) and greedy decode
through the cache, for both configurations' equations (SwiGLU yi-9b,
GELU starcoder2), and the control, the reference with weights rounded
below bfloat16, reading further from the reference than the program."""
import dataclasses

import numpy as np
import pytest

import jax

from chipbench import harness
from chipbench.reference import dense_lm

PREFIX, SUFFIX, DECODE, ROWS = 48, 16, 16, 8


def _ref_cfg(cfg):
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.d_head,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": 1e-6,
            "rope_theta": cfg.rope_theta, "num_hidden_layers": cfg.n_layers,
            "hidden_act": "silu" if cfg.mlp_gated else "gelu_pytorch_tanh",
            "init": {"seed": 0}}


def _serve(arch):
    """Program side: a resumed and a full request through the resume
    engine, each decoded greedily through its cache."""
    from repro import configs
    from repro.models import transformer
    from repro.serve.admit_queue import AdmitQueue
    from repro.serve.kv_index import (KVIndexConfig, KVSlabStore,
                                      MonarchKVIndex)
    from repro.serve.resume import PrefixResumeEngine
    # Reduced widths, but a vocabulary wide enough for near ties, where
    # rounding below bfloat16 changes the best token.
    cfg = dataclasses.replace(configs.get_arch(arch).reduced(), n_layers=3,
                              d_model=256, d_ff=512, vocab_size=8192)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    idx = MonarchKVIndex(KVIndexConfig(n_sets=4, set_ways=32,
                                       admit_after_reads=0,
                                       fingerprint="prefix"),
                         slab_store=KVSlabStore())
    q = AdmitQueue(idx, background=False)
    eng = PrefixResumeEngine(params, cfg, max_seq=PREFIX + SUFFIX + DECODE,
                             index=idx, decode_tokens=DECODE)
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, cfg.vocab_size, (1, PREFIX))
    out = []
    for resumed in (False, True):
        toks = np.concatenate(
            [np.repeat(prefix, ROWS, 0),
             rng.integers(1, cfg.vocab_size, (ROWS, SUFFIX))],
            axis=1).astype(np.int32)
        res = eng.prefill(toks, q.lookup(toks))
        assert (res.resumed_chunks > 0) == resumed
        q.submit_tokens(toks, slabs=res.slabs)
        out.append((toks, eng.decode(res)))
    q.close()
    return cfg, out


@pytest.mark.parametrize("arch", ["yi-9b", "starcoder2-15b"])
def test_program_matches_reference_and_control_does_not(arch):
    cfg, served = _serve(arch)
    rc = _ref_cfg(cfg)
    for toks, dec in served:
        seq = np.concatenate([toks, dec[:, :-1]], axis=1)
        ref = dense_lm.logits(rc, seq, toks.shape[1] - 1)
        program = harness.served_gap(ref, dec).max()
        ctl = dense_lm.logits(rc, seq, toks.shape[1] - 1, quant="fp8")
        control = harness.served_gap(ref, ctl.argmax(-1)).max()
        # bf16 rounding only: the served tokens are the reference's best
        # or within a few bf16 ulps of a logit of magnitude ~4.
        assert program <= 0.05, program
        assert control > 4 * max(program, 0.01), (control, program)


def test_weights_follow_the_init_recipe():
    """The reference draws the program's weights: same key recipe."""
    from repro import configs
    from repro.models import transformer
    cfg = dataclasses.replace(configs.get_arch("starcoder2-15b").reduced(),
                              n_layers=2)
    p = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rc = _ref_cfg(cfg)
    k_embed, k_layers = dense_lm.model_keys(rc)
    w = dense_lm.layer_weights(k_layers[1], rc)
    for name, leaf in (("wq", p["groups"]["b0"]["attn"]["wq"][1]),
                       ("w_down", p["groups"]["b0"]["mlp"]["w_down"][1]),
                       ("unembed", p["embed"]["unembed"])):
        got = np.asarray(leaf, np.float32)
        want = np.asarray(w[name] if name in w
                          else dense_lm.embed_weights(k_embed, rc)[1])
        # The draws are the same; a fused multiply may round an odd draw
        # to the neighbouring bfloat16 value.
        ulp = np.abs(want) * 2.0 ** -7
        assert (np.abs(got - want) <= ulp).all()
        assert (got != want).mean() < 1e-3
