"""Distribution-layer tests: partition specs + divisibility guards,
checkpoint save/restore/restart, elastic reshard, gradient compression,
straggler watchdog, data-pipeline determinism."""
from __future__ import annotations

import os

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean checkout: deterministic-cases fallback
    from _propcheck import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.data import pipeline
from repro.dist import checkpoint, compression, elastic, sharding, straggler
from repro.launch import specs as lspecs
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.train import step as train_step_mod


def _mesh1():
    return make_host_mesh()


# ---------------------------------------------------------------------------
# Partition specs.
# ---------------------------------------------------------------------------

def test_param_specs_structure_matches_params():
    cfg = configs.get_arch("yi-9b").reduced()
    shapes = lspecs.params_shapes(cfg)
    specs = sharding.param_specs(shapes, _mesh1())
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(specs)
    flat = jax.tree_util.tree_leaves_with_path(specs)
    for path, spec in flat:
        assert isinstance(spec, P)


def test_param_specs_rules_on_known_leaves():
    """TP axes land where the rules say (verified against a fake mesh big
    enough to divide everything)."""
    cfg = configs.get_arch("yi-9b")          # FULL config (divisible dims)
    shapes = lspecs.params_shapes(cfg)
    devs = np.asarray(jax.devices() * 4)[:4].reshape(2, 2) \
        if len(jax.devices()) >= 4 else None
    if devs is None:
        # single device: fabricate the mesh via axis sizes 1x1 (guards pass
        # everything through; assert the RULE, pre-guard, instead)
        mesh = _mesh1()
    else:
        mesh = Mesh(devs, ("data", "model"))
    specs = sharding.param_specs(shapes, mesh)

    def find(name):
        for path, s in jax.tree_util.tree_leaves_with_path(specs):
            keys = [getattr(p, "key", "") for p in path]
            if keys[-1] == name:
                return keys, s
        raise KeyError(name)

    keys, s = find("wq")
    assert "groups" in keys          # stacked under the scanned group
    assert s[0] is None              # leading stacked axis unsharded
    keys, s = find("final_ln")
    assert all(ax is None for ax in s)   # norms replicated


def test_divisibility_guard_drops_unshardable_dims():
    mesh = _mesh1()                   # (N, 1) — model axis size 1
    # a dim of size 3 cannot shard over data axis size len(devices) unless 1
    got = sharding._guard(("data", "model"), (3, 5), mesh)
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    if 3 % n_data != 0:
        assert got[0] is None
    assert got == P(*got)             # always a valid PartitionSpec


def test_batch_and_cache_specs_cover_tree():
    cfg = configs.get_arch("gemma3-27b").reduced()
    mesh = _mesh1()
    batch = lspecs.train_batch_specs(cfg, configs.get_shape("train_4k"))
    bs = sharding.batch_specs(batch, mesh)
    assert jax.tree_util.tree_structure(batch) == \
        jax.tree_util.tree_structure(bs)
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, 4, 64))
    cs = sharding.cache_specs(cache, mesh)
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(cs)


# ---------------------------------------------------------------------------
# Checkpointing.
# ---------------------------------------------------------------------------

def _tiny_state():
    cfg = configs.get_arch("yi-9b").reduced()
    return train_step_mod.init_state(jax.random.PRNGKey(0), cfg)


def test_checkpoint_roundtrip(tmp_path):
    state = _tiny_state()
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 7, state, process_index=0)
    step, restored = checkpoint.restore_latest(d, state)
    assert step == 7
    same = jax.tree.map(lambda a, b: bool(jnp.all(jnp.asarray(a) == jnp.asarray(b))),
                        state, restored)
    assert all(jax.tree.leaves(same))


def test_checkpoint_atomic_publish_ignores_partial(tmp_path):
    state = _tiny_state()
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 1, state, process_index=0)
    # simulate a crashed writer: stale tmp dir + a step dir w/o manifest
    os.makedirs(os.path.join(d, "step_9.tmp"))
    os.makedirs(os.path.join(d, "step_5"))
    step, _ = checkpoint.restore_latest(d, state)
    assert step == 1
    checkpoint.save(d, 2, state, process_index=0)  # gc cleans the tmp
    assert not os.path.exists(os.path.join(d, "step_9.tmp"))


def test_checkpoint_keep_last(tmp_path):
    state = _tiny_state()
    d = str(tmp_path / "ckpt")
    for s in range(6):
        checkpoint.save(d, s, state, keep_last=3, process_index=0)
    assert checkpoint.published_steps(d) == [3, 4, 5]


def test_checkpoint_restart_training_equivalence(tmp_path):
    """Kill-and-restart: train 4 steps straight == train 2, checkpoint,
    restore, train 2 more (bitwise on the optimizer step; allclose params)."""
    cfg = configs.get_arch("yi-9b").reduced()
    dcfg = pipeline.DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                               global_batch=2, seed=3)
    step_fn = jax.jit(train_step_mod.make_train_step(cfg))

    def batch(i):
        b = pipeline.batch_at(dcfg, i)
        return {k: jnp.asarray(v) for k, v in b.items()}

    s_direct = train_step_mod.init_state(jax.random.PRNGKey(0), cfg)
    for i in range(4):
        s_direct, _ = step_fn(s_direct, batch(i))

    s_a = train_step_mod.init_state(jax.random.PRNGKey(0), cfg)
    for i in range(2):
        s_a, _ = step_fn(s_a, batch(i))
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 2, s_a, process_index=0)
    step, s_b = checkpoint.restore_latest(d, s_a)
    s_b = jax.tree.map(jnp.asarray, s_b)
    for i in range(step, 4):
        s_b, _ = step_fn(s_b, batch(i))

    assert int(s_direct["opt"]["step"]) == int(s_b["opt"]["step"]) == 4
    for a, b in zip(jax.tree.leaves(s_direct["params"]),
                    jax.tree.leaves(s_b["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Elastic rescaling.
# ---------------------------------------------------------------------------

def test_elastic_reshard_roundtrip(tmp_path):
    state = _tiny_state()
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 3, state, process_index=0)
    new_mesh = _mesh1()     # "new" device topology (same host here)
    step, restored = elastic.resume_elastic(d, state, new_mesh,
                                            run_dir=str(tmp_path))
    assert step == 3
    same = jax.tree.map(lambda a, b: bool(jnp.all(jnp.asarray(a) == jnp.asarray(b))),
                        state, restored)
    assert all(jax.tree.leaves(same))
    assert os.path.exists(os.path.join(str(tmp_path), "scale_events.jsonl"))


@settings(max_examples=20, deadline=None)
@given(gb=st.integers(1, 4096), n=st.integers(1, 64))
def test_elastic_batch_invariants(gb, n):
    per, used = elastic.elastic_batch(gb, n)
    assert per >= 1
    assert used == per * n
    assert used <= max(gb, n)


# ---------------------------------------------------------------------------
# Gradient compression.
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound(rng):
    g = jnp.asarray(rng.normal(size=(1000,)) * 3.0, jnp.float32)
    q, s, pad = compression.quantize_int8(g)
    back = compression.dequantize_int8(q, s, pad, g.shape)
    # error bounded by half a quantization step per block
    err = np.abs(np.asarray(back - g))
    step = np.repeat(np.asarray(s), compression.BLOCK)[: g.shape[0]]
    assert (err <= step * 0.5 + 1e-7).all()


def test_compressed_psum_error_feedback(rng):
    """Over repeated reductions, error feedback keeps the accumulated
    mean-estimate unbiased (residual stays bounded)."""
    mesh = _mesh1()
    if mesh.devices.size != 1:
        pytest.skip("single-device formulation")
    g = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    r = jnp.zeros_like(g)

    def f(g, r):
        return compression.compressed_psum_leaf(g, r, "data")

    fm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()))
    total_err = []
    acc = jnp.zeros_like(g)
    for _ in range(8):
        out, r = fm(g, r)
        acc = acc + out
    # accumulated sum ~= 8 * g (error feedback corrects quantization bias)
    np.testing.assert_allclose(np.asarray(acc) / 8, np.asarray(g),
                               atol=np.abs(np.asarray(g)).max() / 100)


# ---------------------------------------------------------------------------
# Straggler watchdog.
# ---------------------------------------------------------------------------

def test_straggler_policy_escalation():
    cfg = straggler.StragglerConfig(quantile=0.5, slack=2.0,
                                    escalate_after=3, min_history=4)
    w = straggler.StragglerWatchdog(cfg)
    for _ in range(8):
        assert w.observe(1.0) in (straggler.OK,)
    # slow steps: retry, retry, then rejoin on the 3rd consecutive
    assert w.observe(10.0) == straggler.RETRY
    assert w.observe(10.0) == straggler.RETRY
    assert w.observe(10.0) == straggler.REJOIN
    # hysteresis: healthy steps decay suspicion
    assert w.observe(1.0) == straggler.OK
    assert w.observe(10.0) == straggler.RETRY


def test_straggler_single_gc_pause_tolerated():
    w = straggler.StragglerWatchdog(straggler.StragglerConfig(min_history=4))
    for _ in range(8):
        w.observe(1.0)
    assert w.observe(50.0) == straggler.RETRY   # one pause: no eviction
    for _ in range(4):
        assert w.observe(1.0) == straggler.OK


# ---------------------------------------------------------------------------
# Data pipeline determinism / addressability.
# ---------------------------------------------------------------------------

def test_batch_at_deterministic_and_shardable():
    cfg = pipeline.DataConfig(vocab_size=1000, seq_len=32, global_batch=8)
    a = pipeline.batch_at(cfg, step=5)
    b = pipeline.batch_at(cfg, step=5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = pipeline.batch_at(cfg, step=6)
    assert (a["tokens"] != c["tokens"]).any()
    # shards are disjoint functions of (step, shard) and stable
    s0 = pipeline.batch_at(cfg, 5, shard=0, n_shards=4)
    s0b = pipeline.batch_at(cfg, 5, shard=0, n_shards=4)
    np.testing.assert_array_equal(s0["tokens"], s0b["tokens"])
    assert s0["tokens"].shape[0] == 2
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_fingerprints_and_dedup(rng):
    toks = rng.integers(1, 1000, (4, 64)).astype(np.int32)
    fps = pipeline.fingerprint_blocks(toks, 16)
    assert fps.shape == (4, 4)
    fps2 = pipeline.fingerprint_blocks(toks, 16)
    np.testing.assert_array_equal(fps, fps2)
    # same block content -> same fingerprint
    toks2 = toks.copy()
    toks2[1] = toks[0]
    fps3 = pipeline.fingerprint_blocks(toks2, 16)
    np.testing.assert_array_equal(fps3[1], fps3[0])


def test_murmur3_jnp_matches_np(rng):
    x = rng.integers(0, 2 ** 32, 100, dtype=np.uint32)
    a = np.asarray(pipeline.murmur3_fmix32(jnp.asarray(x)))
    b = pipeline.murmur3_np(x)
    np.testing.assert_array_equal(a, b)


def test_ycsb_stream_properties():
    cfg = pipeline.YcsbConfig(n_keys=1000, n_ops=10_000, read_fraction=0.95)
    keys, is_read = pipeline.ycsb_ops(cfg)
    assert abs(is_read.mean() - 0.95) < 0.02
    assert len(np.unique(keys[is_read])) <= 1000
