"""Roofline-analysis tooling: HLO collective walker + analytic FLOPs model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.analysis import (
    collective_bytes_nested,
    flops_bytes_model,
    parse_computations,
    _param_count,
)
from repro.models.config import ArchConfig, ShapeSpec


def test_while_trip_count_scaling():
    """Collectives inside a lax.scan body must be multiplied by its length."""
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >1 device")


def test_walker_counts_scan_collectives():
    hlo = """
HloModule test

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %ar = f32[8]{0} all-reduce(%x), channel_id=1
  ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p2 = (s32[], f32[8]) parameter(0)
  %c = s32[] constant(12)
  ROOT %cmp = pred[] compare(%gte, %c), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  %ag = f32[16]{0} all-gather(%y), channel_id=2
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
    out = collective_bytes_nested(hlo)
    assert out["all-reduce"]["count"] == 12          # scaled by trip count
    assert out["all-reduce"]["bytes"] == 12 * 8 * 4
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 4


def _tiny_cfg(**kw):
    base = dict(arch_id="tiny", family="dense", n_layers=2, d_model=128,
                d_ff=256, vocab=512, attn_kind="gqa", n_heads=4,
                n_kv_heads=4, dtype="float32", remat=False,
                exit_layers=(2,))
    base.update(kw)
    return ArchConfig(**base)


def test_param_count_matches_init():
    """Analytic param count == actual init param count (dense + moe)."""
    from repro.models import model_for
    from repro.nn import tree_size
    for cfg in [
        _tiny_cfg(),
        _tiny_cfg(attn_kind="mla", kv_lora_rank=32, rope_head_dim=8,
                  nope_head_dim=16, v_head_dim=16),
        _tiny_cfg(n_experts=4, n_shared_experts=1, top_k=2, moe_d_ff=64),
    ]:
        model = model_for(cfg)
        real = tree_size(model.init(jax.random.PRNGKey(0), cfg))
        approx = _param_count(cfg)["total"]
        # analytic model skips norms/small vectors: within 5%
        assert abs(real - approx) / real < 0.05, (cfg.arch_id, real, approx)


def test_flops_model_vs_cost_analysis_scanfree():
    """On a scan-free (unrolled CE, no remat) tiny config the analytic
    FLOPs agree with XLA cost_analysis within 2x (cost analysis counts some
    elementwise ops we skip; we must not be 10x off)."""
    cfg = _tiny_cfg()
    from repro.models import model_for
    from repro.train.steps import make_train_state, make_train_step
    from repro.optim import adam

    state, opt = make_train_state(cfg, jax.random.PRNGKey(0), adam(1e-3))
    step = make_train_step(cfg, opt)
    b, s = 4, 64
    batch = {"tokens": jnp.zeros((b, s), jnp.int32),
             "labels": jnp.zeros((b, s), jnp.int32)}
    compiled = jax.jit(step).lower(state, batch).compile()
    cost = compiled.cost_analysis()
    hlo_flops = cost["flops"]
    # correct for the layer scan (2 layers counted once)
    shape = ShapeSpec("t", s, b, "train")
    model = flops_bytes_model(cfg, shape)["flops"]
    # remat off here; analytic assumed remat (x4) -> compare to fwd+bwd (x3)
    analytic = model * 3 / 4
    ratio = analytic / hlo_flops
    assert 0.4 < ratio < 2.5, (analytic, hlo_flops, ratio)


def test_flops_model_modes_ordering():
    cfg = _tiny_cfg()
    f_train = flops_bytes_model(cfg, ShapeSpec("a", 1024, 8, "train"))
    f_pre = flops_bytes_model(cfg, ShapeSpec("b", 1024, 8, "prefill"))
    f_dec = flops_bytes_model(cfg, ShapeSpec("c", 1024, 8, "decode"))
    assert f_train["flops"] > f_pre["flops"] > f_dec["flops"]
    assert f_dec["bytes"] > 0 and f_dec["model_flops"] > 0
