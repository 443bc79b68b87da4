"""Distribution: sharding rules, MoE a2a == dense, dry-run machinery on a
small mesh, multi-pod axis — all in subprocesses with fake devices; plus
hypothesis property coverage of the pure spec logic (no devices needed)."""
import numpy as np
import pytest

from repro.parallel.rules import divisible_spec, qt_specs, spec_for_path


def test_spec_rules():
    import jax
    P = jax.sharding.PartitionSpec
    assert spec_for_path("stack.0.u0.mix.wq", 3, "model") == P(None, "model", None)
    assert spec_for_path("stack.0.u0.mix.wo", 3, "model") == P(None, None, "model")
    assert spec_for_path("embed", 2, "model", stacked=False) == P("model", None)
    assert spec_for_path("stack.0.u0.mlp.experts.wg", 4, "model") == \
        P(None, "model", None, None)
    assert spec_for_path("stack.0.u0.ln1.gamma", 2, "model") == P(None, None)


def test_moe_a2a_equals_dense(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import lm, ModelConfig, MoECfg
from repro.parallel import ParallelCtx
from repro.launch.mesh import make_mesh
mesh = make_mesh(2, 2)
cfg = ModelConfig(name='t', family='moe', n_layers=2, d_model=64, n_heads=4,
      n_kv_heads=2, d_ff=0, vocab=128,
      moe=MoECfg(n_experts=4, top_k=2, d_ff_expert=64, n_shared=1,
                 capacity_factor=8.0))
params = lm.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 128)
lg_d, st_d, _ = lm.forward(cfg, params, {'tokens': toks}, collect_stats=True)
pctx = ParallelCtx(mesh=mesh, data_axes=('data',), model_axis='model')
with mesh:
    lg_a, st_a, _ = lm.forward(cfg, params, {'tokens': toks},
                               collect_stats=True, pctx=pctx)
np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_a), rtol=6e-2, atol=6e-2)
sd = np.asarray(st_d['stack'][0]['u0.mlp.experts.wg']).ravel()
sa = np.asarray(st_a['stack'][0]['u0.mlp.experts.wg']).ravel()
# dense weights stats by gate mass, a2a counts routed tokens with weight 1 —
# same assignment structure, different weighting: require strong correlation
assert np.corrcoef(sd, sa)[0, 1] > 0.9
print('OK')
""", devices=4)
    assert "OK" in out


def test_sharded_train_step_runs(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.models import ModelConfig
from repro.training import Trainer, TrainConfig
from repro.data import DataConfig, token_stream
from repro.parallel import ParallelCtx
from repro.launch.mesh import make_mesh
mesh = make_mesh(2, 4)
pctx = ParallelCtx(mesh=mesh, data_axes=('data',))
cfg = ModelConfig(name='t', family='dense', n_layers=2, d_model=64, n_heads=8,
                  n_kv_heads=4, d_ff=128, vocab=64)
dc = DataConfig(vocab=64, seq_len=32, batch=8, seed=1)
tc = TrainConfig(n_microbatches=2, remat=True, zero1=True, total_steps=20, warmup=2)
with mesh:
    tr = Trainer(cfg, tc, token_stream(dc, 0), pctx=pctx)
    log = tr.run(4)
assert log[-1]['loss'] < log[0]['loss'] + 0.1
# ZeRO-1: master leaves carry a data-sharded dim
specs = [l.sharding.spec for l in jax.tree.leaves(tr.opt_state['m'])]
assert any('data' in str(s) for s in specs), specs
print('OK')
""", devices=8)
    assert "OK" in out


# --------------------------------------------------------------- properties
# Pure spec logic: qt_specs/divisible_spec only read mesh.shape, so a fake
# mesh object drives them without any devices (or even importing a backend).
# Module-level importorskip (the test_property.py idiom) would skip the whole
# file — including the non-hypothesis tests above — so gate only this section.

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:          # pragma: no cover - exercised in minimal envs
    _HAS_HYPOTHESIS = False

    def given(*_a, **_k):    # decorators must exist for the defs below
        return lambda f: pytest.mark.skip(
            reason="property tests need hypothesis (requirements-dev.txt)")(f)

    def settings(*_a, **_k):
        return lambda f: f

    class st:                # noqa: N801 - stand-in for hypothesis.strategies
        @staticmethod
        def integers(*_a, **_k):
            return None

        @staticmethod
        def sampled_from(*_a, **_k):
            return None

        @staticmethod
        def booleans(*_a, **_k):
            return None

SET = settings(max_examples=50, deadline=None)


class _FakeMesh:
    def __init__(self, data, model):
        self.shape = {"data": data, "model": model}


# representative param paths covering every rule family (row, col, expert,
# replicated) both inside and outside the layer stack
_PATHS = [
    "embed", "lm_head",
    "stack.0.u0.mix.wq", "stack.0.u0.mix.wo", "stack.0.u0.mix.wkv_b",
    "stack.0.u0.mix.w_in", "stack.0.u0.mix.w_out",
    "stack.0.u0.mlp.wg", "stack.0.u0.mlp.wd", "stack.0.u0.mlp.w1",
    "stack.0.u0.mlp.w2", "stack.0.u0.mlp.experts.wg",
    "stack.0.u0.mlp.experts.wd", "stack.0.u0.mlp.shared.wg",
    "stack.0.u0.ln1.gamma", "stack.0.u0.mix.qnorm.gamma",
]


def _axis_n(mesh, ax):
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


@SET
@given(st.integers(0, 2**31 - 1), st.sampled_from(_PATHS),
       st.integers(1, 4), st.sampled_from([1, 2, 3, 4, 8]))
def test_divisible_spec_always_divides(seed, path, ndim, model):
    """Every axis that survives divisible_spec divides its dim exactly."""
    rng = np.random.default_rng(seed)
    mesh = _FakeMesh(int(rng.integers(1, 5)), model)
    shape = tuple(int(rng.integers(1, 65)) for _ in range(ndim))
    spec = spec_for_path(path, ndim, "model", stacked="stack" in path)
    out = divisible_spec(spec, shape, mesh)
    assert len(out) == len(shape)
    for dim, ax in zip(shape, out):
        assert dim % _axis_n(mesh, ax) == 0, (path, shape, out)


def _placement(spec, i):
    return spec[i] if i < len(spec) else None


@SET
@given(st.integers(0, 2**31 - 1), st.sampled_from(_PATHS),
       st.sampled_from([1, 2, 4, 8]), st.booleans(), st.booleans())
def test_qt_specs_children_consistent(seed, path, model, lowrank, expert):
    """QuantizedTensor child specs stay mutually consistent and, with a mesh,
    always divide the child shapes.

    Consistency: wint/packed/scale/zero (stored K-major, input dim first)
    share one placement; dinv sits on the input-dim placement; B on the
    output dim, A on the input dim (mesh=None form — the divisibility
    fallback may legitimately drop an axis for one child whose narrower dim
    doesn't divide, e.g. scale's d/g rows)."""
    rng = np.random.default_rng(seed)
    lead = (1,) if "stack" in path else ()
    bits, per = 4, 8
    g = int(rng.choice([8, 16, 32]))
    d = g * per * int(rng.integers(1, 5))         # in-features
    dp = 8 * int(rng.integers(1, 9))              # out-features
    ex = (int(rng.choice([2, 4, 8])),) if expert else ()
    r = int(rng.integers(1, 9))
    shapes = {
        "wint": None, "packed": (*lead, *ex, d // per, dp),
        "scale": (*lead, *ex, d // g, dp), "zero": (*lead, *ex, d // g, dp),
        "dinv": (*lead, *ex, d),
        "B": (*lead, *ex, dp, r) if lowrank else None,
        "A": (*lead, *ex, r, d) if lowrank else None,
    }
    pure = qt_specs(path, shapes, "model")
    nd = len(shapes["packed"])
    in_i, out_i = nd - 2, nd - 1                  # K-major: (d/per, d')
    # shared placement across the packed/scale/zero family
    for k in ("scale", "zero"):
        assert _placement(pure[k], in_i) == _placement(pure["packed"], in_i)
        assert _placement(pure[k], out_i) == _placement(pure["packed"], out_i)
    # dinv rides the input dim; B the output dim; A the input dim
    assert _placement(pure["dinv"], nd - 2) == _placement(pure["packed"], in_i)
    assert _placement(pure["B"], nd - 2) == _placement(pure["packed"], out_i)
    assert _placement(pure["A"], nd - 1) == _placement(pure["packed"], in_i)
    # leading (layer, expert) dims agree everywhere
    for i in range(nd - 2):
        want = _placement(pure["packed"], i)
        for k in ("scale", "zero", "B", "A"):
            assert _placement(pure[k], i) == want, (path, k, i)
    # with a mesh, every emitted spec divides its child's shape
    mesh = _FakeMesh(int(rng.integers(1, 5)), model)
    sized = qt_specs(path, shapes, "model", mesh)
    for k, shape in shapes.items():
        if shape is None:
            continue
        for dim, ax in zip(shape, sized[k]):
            assert dim % _axis_n(mesh, ax) == 0, (path, k, shape, sized[k])


@pytest.mark.slow
def test_dryrun_machinery_multipod(subproc):
    """(pod, data, model) mesh: lower+compile train/prefill/decode for three
    representative smoke archs — the multi-pod axis proof at test scale."""
    out = subproc("""
import jax
import repro.configs as C
C.SHAPES = {'train_4k': (64, 8, 'train'), 'prefill_32k': (64, 4, 'prefill'),
            'decode_32k': (64, 8, 'decode'), 'long_500k': (128, 1, 'decode')}
import repro.launch.steps as S
S.SHAPES = C.SHAPES
from repro.launch.mesh import auto_mesh, make_ctx
from repro.configs import get
mesh = auto_mesh((2, 2, 2), ('pod', 'data', 'model'))
pctx = make_ctx(mesh)
for arch in ['gemma_7b', 'deepseek_v2_lite_16b', 'mamba2_1p3b']:
    cfg = get(arch, smoke=True)
    for shape, kind in [('train_4k', 'train'), ('decode_32k', 'decode')]:
        if kind == 'train':
            fn, args, _ = S.build_train_cell(cfg, pctx, shape)
        else:
            fn, args, _ = S.build_decode_cell(cfg, pctx, shape)
        with mesh:
            fn.lower(*args).compile()
        print(arch, shape, 'OK')
print('ALLOK')
""", devices=8, timeout=900)
    assert "ALLOK" in out
