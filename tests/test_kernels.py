"""Pallas kernels vs pure-jnp oracles — shape/dtype/bits sweeps.

Comparisons are quantization-boundary tolerant: int codes may flip by 1 on
exact .5 ties (fp fusion differences between interpret and XLA paths)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ttq import unpack_weight
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)

SWEEP = [
    # (T, d, dp, bits, g)
    (16, 256, 128, 4, 32),
    (1, 512, 384, 4, 128),     # decode shape
    (9, 256, 256, 8, 32),      # ragged T
    (32, 512, 256, 2, 64),
    (200, 1024, 512, 4, 256),
    (4, 256, 64, 4, 256),      # single group per k-tile
]


def _data(T, d, dp):
    W = jnp.asarray(RNG.standard_normal((dp, d)).astype("float32"))
    D = jnp.asarray(np.exp(RNG.standard_normal(d) * 0.3).astype("float32"))
    x = jnp.asarray(RNG.standard_normal((T, d)).astype("float32"))
    return W, D, x


@pytest.mark.parametrize("T,d,dp,bits,g", SWEEP)
def test_ttq_quantize_kernel(T, d, dp, bits, g):
    W, D, _ = _data(T, d, dp)
    pk, S, Z = ops.ttq_quantize(W, D, bits=bits, group_size=g)
    pk_r, S_r, Z_r = ref.ttq_quantize_ref(W, D, bits=bits, group_size=g)
    u = np.asarray(unpack_weight(pk, d, bits))
    ur = np.asarray(unpack_weight(pk_r, d, bits))
    assert (u != ur).mean() < 2e-3          # boundary ties only
    assert np.abs(u.astype(int) - ur.astype(int)).max() <= 1
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(Z), np.asarray(Z_r), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("T,d,dp,bits,g", SWEEP)
def test_ttq_gemm_kernel(T, d, dp, bits, g):
    W, D, x = _data(T, d, dp)
    pk, S, Z = ref.ttq_quantize_ref(W, D, bits=bits, group_size=g)
    y = ops.ttq_gemm(x, pk, S, Z, dinv=1.0 / D, bits=bits, group_size=g)
    y_r = ref.ttq_gemm_ref(x, pk, S, Z, bits=bits, group_size=g, dinv=1.0 / D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ttq_gemm_dtypes(dtype):
    W, D, x = _data(8, 256, 128)
    x = x.astype(dtype)
    pk, S, Z = ref.ttq_quantize_ref(W, D, bits=4, group_size=32)
    y = ops.ttq_gemm(x, pk, S, Z, bits=4, group_size=32)
    y_r = ref.ttq_gemm_ref(x, pk, S, Z, bits=4, group_size=32)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r.astype(dtype), np.float32),
                               rtol=2e-2, atol=1.0)


def test_gemm_matches_fp_matmul_closely():
    """8-bit quantized gemm ≈ the fp matmul it approximates."""
    W, D, x = _data(16, 512, 128)
    pk, S, Z = ops.ttq_quantize(W, D, bits=8, group_size=32)
    y = ops.ttq_gemm(x, pk, S, Z, dinv=1.0 / D, bits=8, group_size=32)
    y_fp = x @ W.T
    rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 1.2e-2, rel   # ~8-bit groupwise accuracy floor


def test_fallback_path_agrees():
    W, D, x = _data(8, 256, 64)
    pk, S, Z = ops.ttq_quantize(W, D, bits=4, group_size=32, use_pallas=False)
    y_p = ops.ttq_gemm(x, pk, S, Z, bits=4, group_size=32, use_pallas=True)
    y_f = ops.ttq_gemm(x, pk, S, Z, bits=4, group_size=32, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_f),
                               rtol=2e-5, atol=2e-4)
