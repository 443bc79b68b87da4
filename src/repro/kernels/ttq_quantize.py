"""Pallas-TPU online quantization — one streaming pass HBM→VMEM→HBM.

Given the bf16/f32 master weight W (d', d) and the per-prompt activation
diagonal D (d,), produce in a single pass the K-major
:class:`~repro.core.ttq.QuantizedTensor` payload:

    packed (d·bits/32, d') int32   — G[(W∘D)], 32//bits k-rows per int32
    scale  (d/g, d') f32, zero (d/g, d') f32

This is TTQ's per-prompt "find_params" (paper Appendix H) as a memory-bound
streaming kernel: each (bm, bk) tile is read once, scaled by D, turned
K-major in VMEM, reduced to groupwise min/max down the rows on the VPU,
quantized, packed, and written back at ``bits/16`` of the input traffic.  No
inter-tile dependencies → fully parallel grid (d'/bn, d/bk); bk % g == 0
keeps groups tile-local.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quant_kernel(w_ref, d_ref, packed_ref, s_ref, z_ref, *, bits: int,
                  group_size: int):
    qmax = float((1 << bits) - 1)
    per = 32 // bits
    g = group_size
    w = (w_ref[...].astype(jnp.float32) * d_ref[...].astype(jnp.float32)).T
    bk, bn = w.shape                                               # K-major
    wg = w.reshape(bk // g, g, bn)
    wmax = wg.max(axis=1)
    wmin = wg.min(axis=1)
    s = jnp.maximum((wmax - wmin) / qmax, 1e-12)                  # (bk//g, bn)
    z = wmin
    wint = jnp.clip(jnp.round((wg - z[:, None, :]) / s[:, None, :]), 0.0, qmax)
    wint = wint.astype(jnp.int32).reshape(bk // per, per, bn)
    shifts = jax.lax.broadcasted_iota(jnp.int32, wint.shape, 1) * bits
    packed_ref[...] = (wint << shifts).sum(axis=1)
    s_ref[...] = s
    z_ref[...] = z


@functools.partial(
    jax.jit, static_argnames=("bits", "group_size", "bm", "bk", "interpret"))
def ttq_quantize(W: jnp.ndarray, D: jnp.ndarray, *, bits: int = 4,
                 group_size: int = 32, bm: int = 256, bk: int = 512,
                 interpret: bool | None = None):
    """W (d', d) ∘ D (d,) → (packed int32 (d·bits/32, d'), S, Z (d/g, d')).

    ``bm`` tiles d' (the output lanes), ``bk`` tiles d."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    per = 32 // bits
    dp, d = W.shape
    # largest tiles ≤ bm/bk that divide d'/d (e.g. bk 256 for a d=2304
    # shard): VMEM stays bounded by the requested tile
    bm = math.gcd(min(bm, dp), dp)
    bk = math.gcd(min(bk, d), d)
    if bk % group_size or bk % per:
        raise ValueError(f"d={d}: k tile {bk} must be divisible by "
                         f"g={group_size} and {per}")

    kern = functools.partial(_quant_kernel, bits=bits, group_size=group_size)
    packed, S, Z = pl.pallas_call(
        kern,
        grid=(dp // bm, d // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bk // per, bm), lambda i, j: (j, i)),
            pl.BlockSpec((bk // group_size, bm), lambda i, j: (j, i)),
            pl.BlockSpec((bk // group_size, bm), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d // per, dp), jnp.int32),
            jax.ShapeDtypeStruct((d // group_size, dp), jnp.float32),
            jax.ShapeDtypeStruct((d // group_size, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(W, D.reshape(1, d))
    return packed, S, Z
