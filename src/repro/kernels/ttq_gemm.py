"""Pallas-TPU fused dequant matmul — the Marlin analogue for TPU v5e.

y (T, d') = (x (T, d) [∘ D⁻¹]) @ deq(W_packed)

Weights live in HBM K-major, packed ``32//bits`` consecutive k-rows per
int32, (d·bits/32, d') — the 4-bit path moves 4× fewer weight bytes than
bf16, which is the entire speedup mechanism for memory-bound decode (paper
Appendix H, Tables 4-8).  Per k-tile the kernel:

  HBM→VMEM  w_packed (bk·bits/32, bn) int32, scale/zero (bk/g, bn) f32
  VPU       unpack (broadcast over a new sublane axis, shift+mask, merge
            into (bk, bn) rows), dequantize in f32 with the groupwise scale
            broadcast down the rows, round to the activation dtype
  MXU       (bm, bk) @ (bk, bn) in the activation dtype (bf16 when
            serving: one pass, the fp path's precision), accumulated in f32
            into the output tile

The K-major layout is what makes every block legal for the TPU's (8, 128)
tiling at real widths: lanes always carry d', sublanes carry k/per or k/g
rows (bk=256, g=32 → 8 scale rows; 32 or 64 code rows for 4/8 bits).

Grid (T/bm, d'/bn, d/bk) with the k axis marked "arbitrary" (sequential
accumulation); bm/bn default 128 (MXU-aligned), bk 256 (shrunk to the
largest tile that divides d).  The D⁻¹ prescale is applied to the (small)
activation before the call, in f32, then rounded back to the activation
dtype — the operands the jnp path (``core.ttq.ttq_matmul``) and
``ref.ttq_gemm_ref`` also feed their dots.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, w_ref, s_ref, z_ref, o_ref, *, bits: int,
                 group_size: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    per = 32 // bits
    mask = (1 << bits) - 1
    packed = w_ref[...]                                   # (bk//per, bn) int32
    bkp, bn = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (bkp, per, bn), 1) * bits
    wint = (packed[:, None, :] >> shifts) & mask          # (bk//per, per, bn)
    wint = wint.reshape(bkp * per, bn).astype(jnp.float32)
    g = group_size
    ng = s_ref.shape[0]
    s = jnp.broadcast_to(s_ref[...][:, None, :], (ng, g, bn)).reshape(ng * g, bn)
    z = jnp.broadcast_to(z_ref[...][:, None, :], (ng, g, bn)).reshape(ng * g, bn)
    w = (wint * s + z).astype(x_ref.dtype)                # dequantized (bk, bn)
    o_ref[...] += jax.lax.dot_general(x_ref[...], w, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)


def _pad_to(x, m, axis):
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group_size", "bm", "bn", "bk", "interpret",
                     "out_dtype"),
)
def ttq_gemm(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
             zero: jnp.ndarray, dinv: jnp.ndarray | None = None, *,
             bits: int = 4, group_size: int = 32,
             bm: int = 128, bn: int = 128, bk: int = 256,
             interpret: bool | None = None, out_dtype=None) -> jnp.ndarray:
    """x: (..., d) → (..., d'). packed: (d·bits/32, d') int32; S,Z: (d/g, d').

    The MXU operands — x∘dinv and the dequantized weight — take x's dtype;
    products accumulate in f32, returned as ``out_dtype`` (default x's)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    per = 32 // bits
    lead = x.shape[:-1]
    d = x.shape[-1]
    dp = packed.shape[1]
    x2 = x.reshape(-1, d).astype(jnp.float32)
    if dinv is not None:
        x2 = x2 * dinv.astype(jnp.float32)
    x2 = x2.astype(x.dtype)
    T = x2.shape[0]

    # the MXU path needs 16-row alignment (a bf16 sublane tile); interpret
    # mode takes T exactly so the emulated dot presents the same
    # (M, K)×(K, N) shape as the jnp fallback (padding rows changes the
    # backend's gemm micro-kernel choice, which perturbs f32 accumulation
    # order → bf16 rounding-boundary flips)
    bm = min(bm, T if interpret else max(16, ((T + 15) // 16) * 16))
    bk = math.gcd(min(bk, d), d)     # largest tile ≤ bk that divides d
    if bk % group_size or bk % per:
        raise ValueError(f"d={d}: k tile {bk} must be divisible by "
                         f"group_size={group_size} and {per}")
    bn = min(bn, dp)

    x2 = _pad_to(x2, bm, 0)
    packed_p = _pad_to(packed, bn, 1)
    scale_p = _pad_to(scale, bn, 1)
    zero_p = _pad_to(zero, bn, 1)
    Tp, dpp = x2.shape[0], packed_p.shape[1]

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, bits=bits, group_size=group_size),
        grid=(Tp // bm, dpp // bn, d // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // per, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, dpp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, packed_p, scale_p, zero_p)
    return out[:T, :dp].reshape(*lead, dp).astype(out_dtype or x.dtype)
