"""Analytic cost model vs XLA ground truth (unrolled), HLO parser, and
roofline math."""
import jax
import jax.numpy as jnp
import pytest

import repro.configs as configs
from repro.launch.analytic import cell_cost, trunk_fwd_flops, _logit_flops
from repro.launch.hlo_analysis import parse_collectives, _shape_bytes
from repro.launch.roofline import (Roofline, model_flops, roofline_from,
                                   PEAK_FLOPS)
from repro.launch.specs import SHAPES, ShapeSpec


def test_analytic_fwd_flops_vs_xla_unrolled():
    """Unrolled 1-layer dense forward: XLA's cost_analysis is exact there;
    analytic must agree within 10% (elementwise conventions differ)."""
    from repro.models import lm
    from repro.models.common import InitBuilder
    cfg = configs.reduced("qwen3-1.7b").replace(
        n_layers=1, d_model=128, d_ff=256, vocab=512, head_dim=32,
        n_heads=4, n_kv_heads=2, attn_chunk=64, remat=False)
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(0),
                                              jnp.float32))
    B, S = 2, 64
    tokens = jnp.zeros((B, S), jnp.int32)

    def fwd(p, t):
        logits, _ = lm.forward_train(cfg, p, {"tokens": t})
        return logits

    comp = jax.jit(fwd).lower(params, tokens).compile()
    ca = comp.cost_analysis()
    xla_flops = float(ca["flops"])
    ctx = (S + 1) / 2  # S <= chunk → exact causal masking in one block,
    # but the single-block path COMPUTES the full S×S scores:
    ctx_computed = S
    analytic = (trunk_fwd_flops(cfg, B * S, ctx_computed)
                + _logit_flops(cfg, B * S))
    assert abs(analytic - xla_flops) / xla_flops < 0.10, \
        (analytic, xla_flops)


def test_model_flops_conventions():
    cfg = configs.get("qwen3-1.7b")
    t = model_flops(cfg, SHAPES["train_4k"])
    assert t == pytest.approx(6 * cfg.param_count() * 256 * 4096, rel=1e-6)
    d = model_flops(cfg, SHAPES["decode_32k"])
    assert d == pytest.approx(2 * cfg.param_count() * 128, rel=1e-6)
    moe = configs.get("qwen3-moe-30b-a3b")
    assert model_flops(moe, SHAPES["train_4k"]) == pytest.approx(
        6 * moe.active_param_count() * 256 * 4096, rel=1e-6)


def test_roofline_terms_and_dominance():
    rl = roofline_from(flops_per_dev=197e12, bytes_per_dev=819e9 / 2,
                       wire_ici_per_dev=0, wire_dcn_per_dev=0,
                       model_flops_total=197e12 * 0.5, n_chips=1)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(0.5)
    assert rl.dominant == "compute"
    assert rl.useful_ratio == pytest.approx(0.5)
    assert rl.mfu_bound == pytest.approx(0.5)


def test_hlo_shape_bytes():
    assert _shape_bytes("bf16[2,3,4]{2,1,0}") == 48
    assert _shape_bytes("(f32[10], bf16[4])") == 48
    assert _shape_bytes("pred[]") == 1          # scalar = one element


def test_hlo_collective_parsing():
    hlo = """
  %all-reduce = f32[1024]{0} all-reduce(%x), replica_groups=[4,2]<=[8], to_apply=%add
  %ag = bf16[8,128]{1,0} all-gather(%p), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %cp = bf16[64]{0} collective-permute(%y), source_target_pairs={{0,4},{1,5}}
"""
    s = parse_collectives(hlo, pod_size=4)
    kinds = s.by_kind()
    assert kinds["all-reduce"]["count"] == 1
    assert kinds["all-gather"]["count"] == 1
    assert kinds["collective-permute"]["count"] == 1
    # the permute pairs cross pods of size 4 → DCN
    assert s.wire_bytes_dcn >= 128
    # all-reduce of 4096 B in groups of 2 → 2·T·(s-1)/s = 4096
    ar = [o for o in s.ops if o.kind == "all-reduce"][0]
    assert ar.wire_bytes == 4096


def test_cell_cost_sane_magnitudes():
    """Napkin cross-checks: granite-20b train_4k ≈ 6·N·D·(4/3) trunk-ish."""
    cfg = configs.get("granite-20b")
    c = cell_cost(cfg, SHAPES["train_4k"], n_chips=256, dp=16, tp=16,
                  multi_pod=False)
    model = 6 * cfg.param_count() * 256 * 4096
    # remat adds ~1/3; attention + CE chunking add more
    assert model < c.flops_total < 2.6 * model
    # decode is memory-bound: per-dev bytes dominated by weights+cache
    d = cell_cost(cfg, SHAPES["decode_32k"], n_chips=256, dp=16, tp=16,
                  multi_pod=False)
    assert d.hbm_bytes_per_dev > cfg.param_count() * 2 / 16


def test_cell_supported_long_context_rules():
    from repro.launch.specs import cell_supported
    ok, _ = cell_supported(configs.get("falcon-mamba-7b"), "long_500k")
    assert ok
    ok, why = cell_supported(configs.get("granite-20b"), "long_500k")
    assert not ok and "sub-quadratic" in why
