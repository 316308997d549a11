"""Optimizer + roofline-analysis unit tests."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.roofline import analysis, jaxpr_cost
from repro.train import optimizer as opt


# ---------------------------------------------------------------------------
# Optimizer.
# ---------------------------------------------------------------------------

def test_adamw_converges_on_quadratic():
    cfg = opt.OptConfig(peak_lr=0.1, warmup_steps=5, total_steps=200,
                        weight_decay=0.0, clip_norm=100.0)
    target = jnp.asarray([[1.0, -2.0], [3.0, 0.5]], jnp.float32)
    params = {"w": jnp.zeros((2, 2), jnp.float32)}
    state = opt.init_opt_state(params)
    for _ in range(150):
        grads = {"w": params["w"] - target}
        params, state, metrics = opt.adamw_update(cfg, params, state, grads)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)
    assert float(metrics["grad_norm"]) < 1.0


def test_grad_clip():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, gn = opt.clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(10 * 100.0 ** 2), rel=1e-5)
    assert float(opt.global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)
    # under the limit: untouched
    g2 = {"a": jnp.full((4,), 0.1)}
    c2, _ = opt.clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(np.asarray(c2["a"]), 0.1, rtol=1e-6)


def test_lr_schedule_shape():
    cfg = opt.OptConfig(peak_lr=1e-3, warmup_steps=10, total_steps=100,
                        min_lr_ratio=0.1)
    lrs = [float(opt.schedule(cfg, jnp.asarray(s))) for s in range(0, 101, 5)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1e-3, rel=1e-5)
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-2)   # min_lr_ratio floor
    assert all(b <= a * 1.0001 for a, b in zip(lrs[2:], lrs[3:]))  # decays


def test_no_weight_decay_on_norms():
    cfg = opt.OptConfig(peak_lr=0.0, weight_decay=1.0)  # lr=0: pure decay=0
    params = {"ln1": jnp.ones((4,)), "wq": jnp.ones((4, 4))}
    state = opt.init_opt_state(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    new, _, _ = opt.adamw_update(cfg, params, state, zero_g)
    np.testing.assert_array_equal(np.asarray(new["ln1"]), 1.0)


# ---------------------------------------------------------------------------
# Roofline: HLO collective parsing.
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule jit_step

%fused (a: f32[8,16]) -> f32[8,16] {
  ROOT %x = f32[8,16] parameter(0)
}

%body (p: (s32[], bf16[4,128])) -> (s32[], bf16[4,128]) {
  %p = (s32[], bf16[4,128]) parameter(0)
  %g = bf16[4,128]{1,0} get-tuple-element(%p), index=1
  %ag = bf16[8,128]{1,0} all-gather(%g), replica_groups={}, dimensions={0}
  %ar = bf16[4,128]{1,0} all-reduce(%g), to_apply=%fused
  ROOT %t = (s32[], bf16[4,128]) tuple(%i, %ar)
}

%cond (p: (s32[], bf16[4,128])) -> pred[] {
  %p = (s32[], bf16[4,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: bf16[4,128]) -> bf16[4,128] {
  %a = bf16[4,128]{1,0} parameter(0)
  %rs = bf16[2,128]{1,0} reduce-scatter(%a), dimensions={0}, to_apply=%fused
  %w = (s32[], bf16[4,128]) while(%init), condition=%cond, body=%body
  ROOT %r = bf16[4,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_collective_bytes_with_loop_trip_counts():
    out = analysis.collective_bytes(HLO_SAMPLE)
    # reduce-scatter outside the loop: 2*128*2B = 512
    assert out["reduce-scatter"] == 2 * 128 * 2
    # all-gather inside the 10-trip while: 8*128*2B * 10
    assert out["all-gather"] == 8 * 128 * 2 * 10
    assert out["all-reduce"] == 4 * 128 * 2 * 10
    assert out["total"] == (out["all-gather"] + out["all-reduce"]
                            + out["reduce-scatter"])


def test_collective_parser_ignores_instruction_names():
    """Instruction NAMES containing collective substrings must not count."""
    hlo = """
ENTRY %main (a: f32[4]) -> f32[4] {
  %all-reduce-start.1 = f32[4]{0} add(%a, %a)
  ROOT %r = f32[4]{0} negate(%all-reduce-start.1)
}
"""
    out = analysis.collective_bytes(hlo)
    assert out["total"] == 0


def test_roofline_analyze_terms():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    coll = {"total": 50e9 * 0.5, "all-gather": 50e9 * 0.5, "all-reduce": 0,
            "reduce-scatter": 0, "all-to-all": 0, "collective-permute": 0}
    r = analysis.analyze(cost, coll, model_flops_per_device=100e12)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.useful_ratio == pytest.approx(100e12 / 197e12)


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", "v5e"),
    ("cpu", "cpu", "cpu-interpret"),
    ("tpu", "TPU v4", None),            # not in the table: an error
    ("gpu", "NVIDIA H100", None),       # cpu-interpret is for the CPU only
])
def test_machine_table_keyed_by_device_kind(platform, kind, want):
    if want is None:
        with pytest.raises(ValueError, match="device kind"):
            analysis.machine_for(_Device(platform, kind))
        return
    m = analysis.machine_for(_Device(platform, kind))
    assert m.name == want
    if want == "v5e":   # Google Cloud "TPU v5e" published peaks
        assert (m.peak_flops, m.peak_int8_ops, m.hbm_bw) == (
            197e12, 393e12, 819e9)


def test_model_flops_dense_vs_moe():
    from repro import configs
    dense = configs.get_arch("yi-9b")
    moe = configs.get_arch("qwen3-moe-30b-a3b")
    shape = configs.get_shape("train_4k")
    fd = analysis.model_flops(dense, shape, 256)
    fm = analysis.model_flops(moe, shape, 256)
    n_active = analysis.active_param_count(moe)
    n_total_experts = (moe.n_experts * moe.moe_d_ff * moe.d_model
                       * 3 * moe.n_layers)
    # active fraction: top-8 of 128 experts
    assert n_active < n_total_experts
    assert fd > 0 and fm > 0


# ---------------------------------------------------------------------------
# jaxpr trip-count FLOP correction.
# ---------------------------------------------------------------------------

def test_jaxpr_flops_matmul_exact():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    assert jaxpr_cost.step_flops(f, a, b) == 2 * 64 * 32 * 16


def test_jaxpr_flops_counts_scan_trips():
    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    assert jaxpr_cost.step_flops(f, x) == 10 * 2 * 16 ** 3


def test_jaxpr_flops_recurses_remat():
    def f(x):
        @jax.checkpoint
        def g(y):
            return y @ y
        return g(x)
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    assert jaxpr_cost.step_flops(f, x) == 2 * 8 ** 3
