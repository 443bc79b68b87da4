"""The fused TTQ hot loop: kernel-backed decode matmuls (KernelConfig),
single-dispatch requantization (FusedRequantPlan), and the delta gate.

Greedy equality is the contract: flipping the Pallas kernels on must not
change a single emitted token for any covered policy; the fused requant
must reproduce the eager per-leaf tree bit-for-bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KVCacheConfig, KernelConfig, QuantizedTensor, dequant,
                        quantize_params, quantize_weight, ttq_matmul,
                        ttq_policy)
from repro.models import ModelConfig, MoECfg, lm
from repro.quant import QuantizedModel, override
from repro.quant.api import FusedRequantPlan, lowrank_tree
from repro.serving import EngineConfig, TTQEngine

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=96, vocab=128)


@pytest.fixture(scope="module")
def params():
    return lm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prefilled(params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab)
    _, _, stats = lm.prefill(CFG, params, {"tokens": toks}, max_len=20)
    return params, stats, float(toks.size)


def _qts(tree):
    return [l for l in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(l, QuantizedTensor)]


# ---------------------------------------------------------------------------
# e2e: greedy decode bit-identical with kernels on vs off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("bits", [4, 8])
def test_engine_greedy_identical_kernels_on_off(params, kv_dtype, bits):
    """Full engine decode over packed weights: the Pallas ttq_gemm path and
    the jnp fallback must emit the exact same greedy token streams for every
    KV-cache layout — the kernel is a pure perf knob."""
    pol = ttq_policy(bits=bits, group_size=32, rank=0, packed=True,
                     kvcache=KVCacheConfig(dtype=kv_dtype))
    prompts = [[5, 9, 17, 3], [8, 8, 1], [100, 50, 25, 12]]
    outs = {}
    for use in (False, True):
        eng = TTQEngine(CFG, params, pol,
                        EngineConfig(max_slots=2, max_len=48, decode_chunk=2,
                                     use_kernels=use))
        rids = [eng.submit(p, max_new=5) for p in prompts]
        o = eng.run_all()
        outs[use] = [o[r] for r in rids]
        assert eng.n_requants >= 1          # decode ran on quantized weights
        assert eng.kncfg.use_pallas is use
    assert outs[True] == outs[False]


def test_engine_greedy_identical_with_lowrank(params):
    """Low-rank residual + packed kernel path: still token-identical."""
    pol = ttq_policy(bits=4, group_size=32, rank=8, packed=True)
    outs = {}
    for use in (False, True):
        eng = TTQEngine(CFG, params, pol,
                        EngineConfig(max_slots=1, max_len=48,
                                     use_kernels=use))
        rid = eng.submit([5, 9, 17, 3], max_new=5)
        outs[use] = eng.run_all()[rid]
    assert outs[True] == outs[False]


def test_moe_expert_path_kernels_on_off():
    """The vmapped expert matmul dispatches one batched Pallas gemm; logits
    must match the jnp fallback closely and argmax exactly."""
    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                      moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=32,
                                 n_shared=0))
    mparams = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)
    _, state, stats = lm.prefill(cfg, mparams, {"tokens": toks}, max_len=12)
    qp = quantize_params(mparams, stats, ttq_policy(bits=4, group_size=16,
                                                    rank=0, packed=True),
                         count=float(toks.size))
    assert any(qt.packed is not None for qt in _qts(qp))
    tok = jnp.asarray([[7], [11]], jnp.int32)
    pos = jnp.asarray([8, 8], jnp.int32)
    lg_off, _ = lm.decode_step(cfg, qp, state, tok, pos)
    lg_on, _ = lm.decode_step(cfg, qp, state, tok, pos,
                              kcfg=KernelConfig(use_pallas=True))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lg_off, -1)),
                                  np.asarray(jnp.argmax(lg_on, -1)))
    # bf16 residual activations: one-ulp rounding differences are expected
    np.testing.assert_allclose(np.asarray(lg_on), np.asarray(lg_off),
                               rtol=1e-1, atol=5e-2)


# ---------------------------------------------------------------------------
# fused single-dispatch requantization == eager per-leaf tree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", [
    ttq_policy(bits=4, group_size=32, rank=0),
    ttq_policy(bits=4, group_size=32, rank=8),
    ttq_policy(bits=4, group_size=32, rank=0, packed=True),
    ttq_policy(bits=3, group_size=32, rank=0).with_overrides(
        override("*.mix.*", bits=8), override("*.mlp.*", method="rtn")),
], ids=["base", "lowrank", "packed", "mixed"])
def test_fused_requant_matches_per_leaf(prefilled, pol):
    params, stats, count = prefilled
    lrt = lowrank_tree(params, pol)
    eager = quantize_params(params, stats, pol, count=count, lowrank_tree=lrt)
    plan = FusedRequantPlan(params, stats, pol, lowrank_tree=lrt)
    fused = plan.run(params, stats, count, lrt)
    ea, fu = _qts(eager), _qts(fused)
    assert len(ea) == len(fu) > 0
    for a, b in zip(ea, fu):
        assert (a.wint is None) == (b.wint is None)
        assert (a.packed is None) == (b.packed is None)
        codes_a = a.wint if a.wint is not None else a.packed
        codes_b = b.wint if b.wint is not None else b.packed
        np.testing.assert_array_equal(np.asarray(codes_a),
                                      np.asarray(codes_b))
        np.testing.assert_allclose(np.asarray(a.scale), np.asarray(b.scale),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a.dinv), np.asarray(b.dinv),
                                   rtol=1e-6)
        assert (a.bits, a.group_size) == (b.bits, b.group_size)
    # full precision leaves stay identical objects
    fp_paths = [l for l in jax.tree.leaves(fused)
                if not isinstance(l, QuantizedTensor)]
    assert len(fp_paths) == len([l for l in jax.tree.leaves(eager)
                                 if not isinstance(l, QuantizedTensor)])


def test_fused_requant_moe_stacked_experts():
    """4-D (run, expert) stacked weights flatten into the family stack and
    come back per-expert — matching the eager vmapped driver exactly."""
    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                      moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=32,
                                 n_shared=1))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, cfg.vocab)
    _, _, stats = lm.prefill(cfg, params, {"tokens": toks}, max_len=16)
    pol = ttq_policy(bits=4, group_size=16, rank=0)
    eager = quantize_params(params, stats, pol, count=float(toks.size))
    plan = FusedRequantPlan(params, stats, pol)
    fused = plan.run(params, stats, float(toks.size))
    for a, b in zip(_qts(eager), _qts(fused)):
        np.testing.assert_array_equal(np.asarray(a.wint), np.asarray(b.wint))
        np.testing.assert_allclose(np.asarray(a.dinv), np.asarray(b.dinv),
                                   rtol=1e-6)


def test_fused_requant_mixed_rank_overrides(prefilled):
    """Per-layer rank overrides put same-shape leaves in separate families
    (mixed B/A trailing dims cannot share one stacked dispatch) — regression
    for the family-key-missing-rank crash."""
    params, stats, count = prefilled
    pol = ttq_policy(bits=4, group_size=32, rank=8).with_overrides(
        override("*.mix.wq", rank=16))
    lrt = lowrank_tree(params, pol)
    eager = quantize_params(params, stats, pol, count=count, lowrank_tree=lrt)
    plan = FusedRequantPlan(params, stats, pol, lowrank_tree=lrt)
    fused = plan.run(params, stats, count, lrt)
    for a, b in zip(_qts(eager), _qts(fused)):
        np.testing.assert_array_equal(np.asarray(a.wint), np.asarray(b.wint))
        assert (a.B is None) == (b.B is None)
        if a.B is not None:
            assert a.B.shape == b.B.shape
    wq = fused["stack"][0]["u0"]["mix"]["wq"]
    wg = fused["stack"][0]["u0"]["mlp"]["wg"]
    assert wq.B.shape[-1] == 16 and wg.B.shape[-1] == 8


def test_fused_requant_pallas_quantize_kernel(prefilled):
    """policy.kernel.use_pallas + packed routes the family programs through
    the vmapped Pallas ttq_quantize — codes match the jnp closed form up to
    rounding-boundary ties (the test_kernels tolerance), and a full-model
    decode over the kernel-quantized tree stays finite and kernel-served."""
    from repro.core import FUSED_KERNELS
    from repro.core.ttq import unpack_weight

    params, stats, count = prefilled
    pol = ttq_policy(bits=4, group_size=32, rank=0, packed=True,
                     kernel=FUSED_KERNELS)
    plan = FusedRequantPlan(params, stats, pol)
    fused = plan.run(params, stats, count)
    ref = quantize_params(params, stats, pol.with_(kernel=KernelConfig()),
                          count=count)
    n_packed = 0
    for a, b in zip(_qts(ref), _qts(fused)):
        assert b.packed is not None
        n_packed += 1
        ua = np.asarray(unpack_weight(a.packed, a.in_features, a.bits))
        ub = np.asarray(unpack_weight(b.packed, b.in_features, b.bits))
        assert (ua != ub).mean() < 2e-3          # boundary ties only
        assert np.abs(ua.astype(int) - ub.astype(int)).max() <= 1
        np.testing.assert_allclose(np.asarray(a.scale), np.asarray(b.scale),
                                   rtol=1e-5)
    assert n_packed > 0
    toks = jax.random.randint(jax.random.PRNGKey(11), (2, 8), 0, CFG.vocab)
    lg, _, _ = lm.forward(CFG, fused, {"tokens": toks},
                          kcfg=pol.kernel)
    assert bool(jnp.isfinite(lg).all())


def test_fused_plan_is_single_dispatch_per_family(prefilled, monkeypatch):
    """One compiled-program call per weight family — not one per leaf."""
    params, stats, count = prefilled
    pol = ttq_policy(bits=4, group_size=32, rank=0)
    plan = FusedRequantPlan(params, stats, pol)
    calls = []
    for key, fn in plan._family_fns.items():
        plan._family_fns[key] = (lambda *a, _f=fn, _k=key:
                                 calls.append(_k) or _f(*a))
    plan.run(params, stats, count)
    assert len(calls) == len(plan.families)
    assert plan.n_layers == 7 and len(plan.families) < plan.n_layers


# ---------------------------------------------------------------------------
# delta gate
# ---------------------------------------------------------------------------

def test_drift_gate_threshold_semantics(prefilled):
    params, stats, count = prefilled
    qm = QuantizedModel(params, ttq_policy(bits=4, group_size=32, rank=0))
    qm.calibrate(stats, count)
    assert qm.requantize() is not None          # baseline snapshot
    n_all = qm.last_requant_layers
    assert n_all > 0 and qm.last_skipped_layers == 0

    qm.calibrate(stats, count)
    qm.requantize(threshold=0.0)                # 0 ⇒ every layer requantizes
    assert qm.last_requant_layers == n_all
    assert qm.last_skipped_layers == 0

    before = dict(qm._qt_by_path)
    qm.calibrate(stats, count)
    out = qm.requantize(threshold=float("inf"))  # ∞ ⇒ none; QTs reused
    assert qm.last_requant_layers == 0
    assert qm.last_skipped_layers == n_all
    for ps, qt in qm._qt_by_path.items():
        assert qt is before[ps]
    assert out is not None                       # tree still returned


def test_drift_gate_partial_on_domain_shift(params):
    """Stable stream → mass skips; a shifted stream wakes drifted layers."""
    toks_a = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, CFG.vocab)
    toks_b = jnp.full((2, 16), 3, jnp.int32)    # degenerate shifted domain
    _, _, st_a = lm.prefill(CFG, params, {"tokens": toks_a}, max_len=20)
    _, _, st_b = lm.prefill(CFG, params, {"tokens": toks_b}, max_len=20)
    qm = QuantizedModel(params, ttq_policy(bits=4, group_size=32, rank=0),
                        halflife=1.0)
    qm.calibrate(st_a, 32.0)
    qm.requantize()
    qm.calibrate(st_a, 32.0)                    # same domain again
    qm.requantize(threshold=0.05)
    stable_requants = qm.last_requant_layers
    qm.calibrate(st_b, 32.0)                    # domain shift
    qm.requantize(threshold=0.05)
    assert qm.last_requant_layers > stable_requants
    assert qm.last_skipped_layers < qm._plan.n_layers


def test_gated_decode_matches_full(prefilled):
    """A gate-skipped tree still decodes: greedy tokens equal the full
    requant (stats unchanged ⇒ reused QTs are the same quantization)."""
    params, stats, count = prefilled
    outs = {}
    for thr in (-1.0, float("inf")):
        eng = TTQEngine(CFG, params, ttq_policy(bits=8, group_size=32, rank=0),
                        EngineConfig(max_slots=1, max_len=48,
                                     requant_threshold=thr))
        for p in ([5, 9, 17, 3], [8, 8, 1]):
            eng.submit(p, max_new=4)
        o = eng.run_all()
        outs[thr] = [o[r] for r in sorted(o)]
        if thr == float("inf"):
            assert eng.layers_skipped > 0
    assert outs[-1.0] == outs[float("inf")]


def test_double_buffer_swap_semantics(prefilled):
    """Default: the requantize call swaps deterministically.  Opt-in
    double_buffer: the previous tree keeps serving until the pending one is
    device-ready, then decode_params swaps to it."""
    params, stats, count = prefilled
    pol = ttq_policy(bits=4, group_size=32, rank=0)
    qm = QuantizedModel(params, pol)
    qm.calibrate(stats, count)
    t1 = qm.requantize()
    t2 = qm.requantize()
    assert qm.decode_params is t2 and qm._pending is None   # deterministic

    db = QuantizedModel(params, pol, double_buffer=True)
    db.calibrate(stats, count)
    b1 = db.requantize()
    assert db.decode_params is b1                # first tree serves directly
    b2 = db.requantize()
    assert db._pending is b2 or db.qparams is b2  # parked until ready
    jax.block_until_ready(jax.tree.leaves(b2))
    assert db.decode_params is b2                # ready → swapped
    assert db._pending is None


# ---------------------------------------------------------------------------
# bits=8 code-dtype regression (the int8 overflow hazard)
# ---------------------------------------------------------------------------

def test_bits8_roundtrip_packed_vs_unpacked():
    """8-bit codes span 0..255: the packed path must dequantize and matmul
    identically to the unpacked path (a signed-int8 cast would wrap codes
    ≥ 128 and corrupt half the range)."""
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((32, 64)).astype("float32")) * 4.0
    D = jnp.asarray(np.exp(rng.standard_normal(64) * 0.3).astype("float32"))
    pol_packed = ttq_policy(bits=8, group_size=32, rank=0, packed=True)
    pol_plain = ttq_policy(bits=8, group_size=32, rank=0, packed=False)
    qt_p = quantize_weight(W, D, pol_packed)
    qt_u = quantize_weight(W, D, pol_plain)
    assert qt_p.packed is not None and qt_u.wint is not None
    assert int(jnp.max(qt_u.wint)) > 127        # codes really use 128..255
    Wp, Wu = dequant(qt_p), dequant(qt_u)
    np.testing.assert_allclose(np.asarray(Wp), np.asarray(Wu),
                               rtol=1e-6, atol=1e-6)
    x = jnp.asarray(rng.standard_normal((3, 64)).astype("float32"))
    yp = ttq_matmul(x, qt_p)
    yu = ttq_matmul(x, qt_u)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yu),
                               rtol=1e-5, atol=1e-5)
    # and the Pallas kernel path agrees with both
    yk = ttq_matmul(x, qt_p, kcfg=KernelConfig(use_pallas=True))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yu),
                               rtol=1e-4, atol=1e-4)
