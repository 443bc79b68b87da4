"""TTQ — the paper's contribution as a composable JAX module.

Lifecycle (paper Fig. 1b):

    prefill (full precision, stats tap on)          decode (quantized)
    ────────────────────────────────────►  quantize ────────────────►
    stats[layer] += Σ_t |x_t|^p                 │    int4 matmul w/
                                                ▼    prescaled x
                             D = (stats^{1/p}+λ)^α
                             W_int,S,Z = G[(W−BA)∘D]

Three entry points:

* :func:`calibrate`      — stats pytree → per-layer D vectors.
* :func:`quantize_tree`  — fp param pytree (+ D tree, + optional low-rank tree)
                           → :class:`QuantizedTensor` pytree (packed or fake).
* :func:`ttq_linear`     — the functional linear used inside model forwards;
                           dispatches on the param type (fp / QuantizedTensor).

``QuantizedTensor`` is a pytree-registered dataclass so quantized parameter
trees flow through jit / pjit / shard_map like any other params.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .awq import AWQConfig, awq_quantize, diag_from_stats
from .lowrank import svd_factors
from .policy import QuantPolicy
from .qdq import pack_bits, unpack_bits


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Groupwise-quantized weight: y = (x/D)·deq(Wint) [+ B(Ax)].

    Codes and scales are stored **K-major** — the contraction dim d leads —
    so the Pallas ``ttq_gemm`` streams (bk/per, bn) code tiles and
    (bk/g, bn) scale tiles that obey the TPU's (8, 128) block tiling at
    real widths.  ``packed`` holds int32 data (d·bits/32, d'), ``32//bits``
    consecutive k-rows per int32 (:func:`pack_weight`), when the policy's
    packed path is on, else ``wint`` holds **uint8** codes (d, d') in
    [0, 2^bits−1].  Exactly one of the two is set.  Codes are unsigned on
    purpose: 8-bit codes span 0..255, which a signed int8 store would wrap
    — unpacked-on-the-fly codes stay int32 for the same reason (bits=8
    round-trip regression in tests/test_fused_path.py).
    """

    wint: Optional[jnp.ndarray]      # (d, d') uint8 | None
    packed: Optional[jnp.ndarray]    # (d*bits//32, d') int32 | None
    scale: jnp.ndarray               # (d//g, d') f32
    zero: jnp.ndarray                # (d//g, d') f32
    dinv: jnp.ndarray                # (d,) f32 — activation prescale 1/D
    B: Optional[jnp.ndarray]         # (d', r) | None
    A: Optional[jnp.ndarray]         # (r, d) | None
    bits: int = 4
    group_size: int = 32
    out_features: int = 0
    in_features: int = 0

    def tree_flatten(self):
        children = (self.wint, self.packed, self.scale, self.zero, self.dinv,
                    self.B, self.A)
        aux = (self.bits, self.group_size, self.out_features, self.in_features)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def packable(bits: int, d: int) -> bool:
    """Whether ``bits``-bit codes of a d-wide input pack into int32 rows."""
    return 32 % bits == 0 and d % (32 // bits) == 0


def pack_weight(wint: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Row-layout codes (..., d', d) → K-major packed (..., d·bits/32, d')
    int32: int32 row i holds k-rows ``i·per .. i·per+per−1`` (per =
    32//bits), lowest bits first."""
    return pack_bits(wint.astype(jnp.int32), bits).swapaxes(-1, -2)


def unpack_weight(packed: jnp.ndarray, d: int, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack_weight`: (..., d·bits/32, d') → (..., d, d')
    int32."""
    return unpack_bits(packed.swapaxes(-1, -2), d, bits).swapaxes(-1, -2)


def dequantize_kmajor(wint: jnp.ndarray, scale: jnp.ndarray,
                      zero: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """K-major codes (d, d') with (d/g, d') scale/zero → f32 (d, d').  Only
    the leading (contraction) dim is split, so a weight sharded on d' never
    needs gathering to dequantize."""
    d, dp = wint.shape
    g = group_size
    w = wint.reshape(d // g, g, dp).astype(jnp.float32)
    return (w * scale[:, None, :] + zero[:, None, :]).reshape(d, dp)


def calibrate(stats: Any, counts: Any, acfg: AWQConfig) -> Any:
    """Map accumulated Σ|x|^p stats pytree → D pytree (matching structure)."""
    return jax.tree.map(lambda s, n: diag_from_stats(s, n, acfg), stats, counts)


def quantize_weight(W: jnp.ndarray, D: jnp.ndarray, policy: QuantPolicy,
                    B: Optional[jnp.ndarray] = None,
                    A: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    """Quantize one (d', d) weight online given its activation diagonal D."""
    qcfg = policy.qcfg
    if qcfg.layout != "row":
        qcfg = dataclasses.replace(qcfg, layout="row")
    Wf = W.astype(jnp.float32)
    if B is not None and A is not None and policy.rank > 0:
        Wf = Wf - B.astype(jnp.float32) @ A.astype(jnp.float32)
    else:
        B = A = None
    wint, S, Z = awq_quantize(Wf, D, qcfg)
    dinv = (1.0 / D).astype(jnp.float32)
    packed = wint_out = None
    if policy.packed and packable(qcfg.bits, W.shape[1]):
        packed = pack_weight(wint, qcfg.bits)
    else:
        wint_out = wint.T
    return QuantizedTensor(
        wint=wint_out, packed=packed, scale=S.T, zero=Z.T, dinv=dinv, B=B, A=A,
        bits=qcfg.bits, group_size=qcfg.group_size,
        out_features=W.shape[0], in_features=W.shape[1],
    )


def init_lowrank_tree(params: Any, policy: QuantPolicy, is_weight) -> Any:
    """Offline, data-free: top-r SVD factors per quantizable 2-D weight.

    ``is_weight(path, leaf) → bool`` decides eligibility. Returns a pytree of
    {'B','A'} dicts (None where ineligible) with the same treedef as params.
    """
    if policy.rank <= 0:
        return jax.tree.map(lambda _: None, params)

    def per_leaf(path, leaf):
        if is_weight(path, leaf) and leaf.ndim == 2:
            B, A = svd_factors(leaf, policy.rank)
            return {"B": B, "A": A}
        return None

    return jax.tree_util.tree_map_with_path(per_leaf, params)


def dequant(qt: QuantizedTensor) -> jnp.ndarray:
    """Effective fp weight  Ŵ = deq(Wint)∘D⁻¹ [+ BA]  — reference/debug path."""
    W = _codes_f32(qt).T * qt.dinv[None, :]
    if qt.B is not None:
        W = W + qt.B.astype(jnp.float32) @ qt.A.astype(jnp.float32)
    return W


def _codes_f32(qt: QuantizedTensor) -> jnp.ndarray:
    """Dequantized K-major weight deq(Wint) (d, d') f32, D⁻¹ not applied.
    Packed codes unpack to int32: 8-bit codes span 0..255, which a signed
    int8 cast would wrap."""
    wint = qt.wint
    if wint is None:
        wint = unpack_weight(qt.packed, qt.in_features, qt.bits)
    return dequantize_kmajor(wint, qt.scale, qt.zero, qt.group_size)


def ttq_matmul(x: jnp.ndarray, qt: QuantizedTensor, *,
               use_kernel: bool = False, kcfg=None,
               pctx=None, tp=None) -> jnp.ndarray:
    """y = x @ Ŵᵀ for x: (..., d).  Kernel path uses the Pallas ttq_gemm.

    ``kcfg`` (:class:`~repro.core.policy.KernelConfig`) is the policy-driven
    dispatch switch threaded by the model stack: ``use_pallas=True`` (or the
    legacy ``use_kernel`` flag) sends packed weights through ``ttq_gemm``.
    Both paths prescale x∘D⁻¹ in f32 on the (small) activation and feed
    the dot operands (x∘D⁻¹ and the dequantized weight) in x's dtype with
    f32 accumulation — the fp path's precision; the low-rank branch runs in
    fp on the *unscaled* x either way (BA was subtracted before scaling).

    ``pctx``/``tp``: with an active mesh and a TP role hint ('row'|'col')
    from the call site's sharding rule, the kernel dispatch is shard_map'd so
    each device runs ttq_gemm on its local weight shard; the low-rank BA
    correction is tiny and stays outside the wrap (plain GSPMD).
    """
    if kcfg is not None and kcfg.use_pallas:
        use_kernel = True
    if use_kernel and qt.packed is not None:
        from repro.kernels import ops as kops  # local import: kernels are optional
        kw = kcfg.gemm_kw if kcfg is not None else {}
        y = kops.ttq_gemm_tp(x, qt.packed, qt.scale, qt.zero, qt.dinv,
                             bits=qt.bits, group_size=qt.group_size,
                             pctx=pctx, tp=tp, **kw)
    else:
        # the kernel's operands over the same flattened (T, d)×(d, d') gemm
        # shape it presents, so in interpret mode both paths hit the same
        # backend micro-kernel and the same f32 reduction order (the
        # greedy-equality contract: flipping the kernel on must not move a
        # single token); the cast back to x.dtype mirrors ttq_gemm's epilogue
        lead = x.shape[:-1]
        xs = x.reshape(-1, x.shape[-1]).astype(jnp.float32) * qt.dinv
        y = jax.lax.dot_general(xs.astype(x.dtype),
                                _codes_f32(qt).astype(x.dtype),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y.reshape(*lead, -1).astype(x.dtype)
    if qt.B is not None:
        # f32 results, then the casts: a d-sharded x (column-parallel TP)
        # reduces its partial sums in f32, as the single-device dot does
        xa = jnp.einsum("...d,rd->...r", x, qt.A.astype(x.dtype),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        y = y + jnp.einsum("...r,or->...o", xa, qt.B.astype(x.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
    return y


def ttq_linear(x: jnp.ndarray, w, **kw) -> jnp.ndarray:
    """Dispatch: fp weight (d', d) → plain matmul; QuantizedTensor → ttq path."""
    if isinstance(w, QuantizedTensor):
        return ttq_matmul(x, w, **kw)
    return jnp.einsum("...d,od->...o", x, w)


# ---------------------------------------------------------------------------
# whole-model quantization now lives in repro.quant.api — thin shims below
# keep historical imports (repro.core.quantize_params, ...) working.
# ---------------------------------------------------------------------------

from repro.quant.api import (STAT_ALIAS, _lookup_stats, _path_str,  # noqa: E402
                             _stats_key, _tree_get, quantize_params)
