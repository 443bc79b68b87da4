"""Whole-model TTQ: quantize_params joins stats↔weights by path; dequant
matches the closed form; policy skip patterns honored; MoE per-expert stats."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AWQConfig, QuantizedTensor, awq_qdq, dequant,
                        quantize_params, ttq_policy)
from repro.core.awq import diag_from_stats
from repro.models import ModelConfig, MoECfg, lm

CFG = ModelConfig(name="t", family="dense", n_layers=3, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=96, vocab=128)


def _prefilled(cfg, seed=0, B=2, S=16):
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, cfg.vocab)
    _, state, stats = lm.prefill(cfg, params, {"tokens": toks}, max_len=S + 4)
    return params, stats, B * S


def test_quantize_params_joins_by_path():
    params, stats, count = _prefilled(CFG)
    pol = ttq_policy(bits=4, group_size=32, rank=0)
    qp = quantize_params(params, stats, pol, count=count)
    qts = [l for l in jax.tree.leaves(
        qp, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(l, QuantizedTensor)]
    # dense layer: wq, wk, wv, wo, wg, wu, wd = 7
    assert len(qts) == 7
    # embed / lm_head / norms untouched
    assert qp["embed"].dtype == params["embed"].dtype


def test_dequant_matches_closed_form():
    """vmapped whole-tree quantization == per-weight awq_qdq closed form."""
    params, stats, count = _prefilled(CFG)
    pol = ttq_policy(bits=4, group_size=32, rank=0)
    qp = quantize_params(params, stats, pol, count=count)
    layer = 1
    W = params["stack"][0]["u0"]["mix"]["wq"][layer].astype(jnp.float32)
    stat = stats["stack"][0]["u0.mix.wq"][layer]
    D = diag_from_stats(stat, jnp.float32(count), pol.acfg)
    expect = awq_qdq(W, D, pol.qcfg)
    qt_stack = qp["stack"][0]["u0"]["mix"]["wq"]
    qt = jax.tree.map(lambda l: l[layer], qt_stack)
    got = dequant(qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_skip_patterns():
    params, stats, count = _prefilled(CFG)
    pol = ttq_policy(bits=4, group_size=32).with_(
        skip=("embed*", "lm_head", "*norm*", "router*", "*wq", "*wk", "*wv"))
    qp = quantize_params(params, stats, pol, count=count)
    wq = qp["stack"][0]["u0"]["mix"]["wq"]
    assert not isinstance(wq, QuantizedTensor)
    wo = qp["stack"][0]["u0"]["mix"]["wo"]
    assert isinstance(wo, QuantizedTensor)


def test_moe_per_expert_quantization():
    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                      moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=48,
                                 n_shared=1))
    params, stats, count = _prefilled(cfg)
    st = stats["stack"][0]
    assert st["u0.mlp.experts.wg"].shape == (2, 4, 64)   # (L, E, D)
    assert st["u0.mlp.experts.wd"].shape == (2, 4, 48)
    pol = ttq_policy(bits=4, group_size=16, rank=0)
    qp = quantize_params(params, stats, pol, count=count)
    qt = qp["stack"][0]["u0"]["mlp"]["experts"]["wg"]
    assert isinstance(qt, QuantizedTensor)
    assert qt.wint.shape == (2, 4, 64, 48)               # (L, E, D, F)
    assert qt.dinv.shape == (2, 4, 64)                   # per-expert D!
    # per-expert diagonals differ (different token subsets)
    d0, d1 = np.asarray(qt.dinv[0, 0]), np.asarray(qt.dinv[0, 1])
    assert not np.allclose(d0, d1)


def test_lowrank_residual_quantization():
    params, stats, count = _prefilled(CFG)
    pol = ttq_policy(bits=4, group_size=32, rank=8)
    qp = quantize_params(params, stats, pol, count=count)
    qt_stack = qp["stack"][0]["u0"]["mlp"]["wg"]
    assert qt_stack.B is not None and qt_stack.A is not None
    assert qt_stack.B.shape == (3, 96, 8) and qt_stack.A.shape == (3, 8, 64)
    # effective weight closer to original than rank-0 version
    pol0 = ttq_policy(bits=4, group_size=32, rank=0)
    qp0 = quantize_params(params, stats, pol0, count=count)
    W = params["stack"][0]["u0"]["mlp"]["wg"][0].astype(jnp.float32)
    e_lr = float(jnp.mean((dequant(jax.tree.map(lambda l: l[0], qt_stack)) - W) ** 2))
    e_0 = float(jnp.mean((dequant(jax.tree.map(
        lambda l: l[0], qp0["stack"][0]["u0"]["mlp"]["wg"])) - W) ** 2))
    assert e_lr < e_0


def test_rtn_protects_non_weight_params():
    """RTN (stats-free) must not mistake stacked 1-D params (norm scales)
    for 2-D weights — regression for the scan-axis-mismatch bug."""
    from repro.core import QuantPolicy
    params, _, _ = _prefilled(CFG)
    pol = QuantPolicy(method="rtn")
    qp = quantize_params(params, None, pol)
    g = qp["stack"][0]["u0"]["ln1"]["gamma"]
    assert not isinstance(g, QuantizedTensor)
    assert isinstance(qp["stack"][0]["u0"]["mix"]["wq"], QuantizedTensor)
    # quantized forward still runs
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 128)
    lg, _, _ = lm.forward(CFG, qp, {"tokens": toks})
    assert not bool(jnp.isnan(lg).any())


def test_quantized_forward_runs():
    params, stats, count = _prefilled(CFG)
    pol = ttq_policy(bits=8, group_size=32, rank=0)
    qp = quantize_params(params, stats, pol, count=count)
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0, 128)
    lg_q, _, _ = lm.forward(CFG, qp, {"tokens": toks})
    lg_f, _, _ = lm.forward(CFG, params, {"tokens": toks})
    assert not bool(jnp.isnan(lg_q).any())
    # 8-bit forward stays close to fp in logit space
    assert float(jnp.abs(lg_q - lg_f).mean()) < 0.5
