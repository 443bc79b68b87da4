"""Pallas-TPU fused dequant decode-attention — the KV-cache analogue of
``ttq_gemm``.

o (B,H,1,Dh) = softmax(q·deq(K_codes)ᵀ/√Dh) · deq(V_codes)

The cache lives in HBM as int8 codes (1 B/elem) or int4 packed 8-per-int32
(0.5 B/elem) plus f32 per-(head, token, group) scales — decode attention is
memory-bound, so moving ~half (int8) or ~quarter (int4) of the bf16 bytes is
the entire speedup mechanism (EXPERIMENTS.md §Roofline).  Per S-tile the
kernel:

  HBM→VMEM  k/v codes (bs, Dh·bits/32 or bs, Dh) + scales (bs, Dh/g)
  VPU       unpack nibbles (shift+mask, int4 only), dequantize to f32 with
            the groupwise scale broadcast — the cache is NEVER materialized
            at bf16 in HBM
  MXU       (G, Dh) @ (Dh, bs) scores with q and k in q's dtype (bf16 when
            serving); online-softmax accumulate p·v (v in q's dtype, p as a
            hi/lo pair of it) into a (G, Dh) f32 output tile
            (flash-decoding over the S axis)

Grid (B, Hkv, S/bs) with the S axis "arbitrary" (sequential — the running
max/denominator/accumulator live in VMEM scratch, initialized at s==0 and
written out at the last tile).  ``cur_pos`` rides in SMEM as a
scalar-prefetch argument (as does the paged kernel's block table); slots
beyond it are masked with an explicit where (NOT exp(-inf - -inf), which
would poison fully-masked tiles).

Validated in interpret mode on CPU (this container) against
``ref.kv_attn_ref``; ``ops.kv_decode_attention`` is the public wrapper with
the ``use_pallas=False`` escape hatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dequant_tile(codes, scales, *, bits: int, group_size: int, Dh: int):
    """codes (bs, Dc) int8/int32, scales (bs, Dh//g) f32 → (bs, Dh) f32."""
    bs = codes.shape[0]
    if bits == 8:
        w = codes.astype(jnp.float32)
    else:
        shifts = (jnp.arange(8, dtype=jnp.int32) * 4)[None, None, :]
        w = (codes[:, :, None] >> shifts) & 0xF                # (bs, Dh//8, 8)
        w = w.reshape(bs, Dh).astype(jnp.float32) - 8.0
    g = group_size or Dh
    s = scales.astype(jnp.float32)
    if g != Dh:
        s = jnp.repeat(s, g, axis=-1)                          # (bs, Dh)
    return w * s


def _scores(q, k):
    """(G, Dh) queries · (bs, Dh) keys → (G, bs) f32: operands in q's dtype
    (bf16 when serving: one MXU pass, the fp path's precision), f32
    accumulation."""
    return jax.lax.dot_general(q, k.astype(q.dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _weighted_values(p, v, dtype):
    """(G, bs) f32 softmax weights · (bs, Dh) values → (G, Dh) f32.  The
    values enter as ``dtype`` (q's); the weights as a hi/lo pair of it (two
    MXU passes), which carries p to ~16 bits when ``dtype`` is bf16: the
    unnormalized online-softmax weights are rounded per tile against a
    running max, so one bf16 rounding of them would not match the oracle's
    normalized softmax even in order."""
    v = v.astype(dtype)
    hi = p.astype(dtype)
    lo = (p - hi.astype(jnp.float32)).astype(dtype)
    dims = (((1,), (0,)), ((), ()))
    return (jax.lax.dot_general(hi, v, dims, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(lo, v, dims,
                                  preferred_element_type=jnp.float32))


def _attn_kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, bits: int, group_size: int,
                 soft_cap: float, bs: int, Dh: int, n_s: int):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = pos_ref[pl.program_id(0)]
    q = q_ref[0, 0]                                            # (G, Dh)
    k = _dequant_tile(kq_ref[0, 0], ks_ref[0, 0], bits=bits,
                      group_size=group_size, Dh=Dh)            # (bs, Dh)
    s = _scores(q, k)                                          # (G, bs)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    ki = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = ki <= cur
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]                    # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit mask-zeroing: a fully-masked tile must contribute 0, not
    # exp(NEG_INF - NEG_INF) = 1 per slot
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)               # (G, bs)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    v = _dequant_tile(vq_ref[0, 0], vs_ref[0, 0], bits=bits,
                      group_size=group_size, Dh=Dh)            # (bs, Dh)
    pv = _weighted_values(p, v, q.dtype)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _paged_attn_kernel(bt_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, bits: int,
                       group_size: int, soft_cap: float, bs: int, Dh: int,
                       n_s: int):
    """Flash-decoding over the *block table* instead of a contiguous S axis.

    Identical online-softmax math to :func:`_attn_kernel`; the only paged
    difference is upstream — the k/v BlockSpecs index the (NB, Hkv, bs, ·)
    pool through the scalar-prefetched block table, so tile ``s`` of slot
    ``b`` streams physical block ``bt[b, s]`` HBM→VMEM.  Tiles past
    ``cur_pos`` (sink/stale blocks) are masked here exactly like padding."""
    b = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = pos_ref[b]
    q = q_ref[0, 0]                                            # (G, Dh)
    k = _dequant_tile(kq_ref[0, 0], ks_ref[0, 0], bits=bits,
                      group_size=group_size, Dh=Dh)            # (bs, Dh)
    s = _scores(q, k)                                          # (G, bs)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    ki = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = ki <= cur
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]                    # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)               # (G, bs)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    v = _dequant_tile(vq_ref[0, 0], vs_ref[0, 0], bits=bits,
                      group_size=group_size, Dh=Dh)            # (bs, Dh)
    pv = _weighted_values(p, v, q.dtype)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "scale",
                                             "soft_cap", "interpret"))
def ttq_paged_decode_attention(q: jnp.ndarray, kq: jnp.ndarray,
                               ks: jnp.ndarray, vq: jnp.ndarray,
                               vs: jnp.ndarray, block_table: jnp.ndarray,
                               cur_pos: jnp.ndarray, *, bits: int = 8,
                               group_size: int = 0, scale: float | None = None,
                               soft_cap: float = 0.0,
                               interpret: bool | None = None) -> jnp.ndarray:
    """q: (B,H,1,Dh); kq/vq: (NB,Hkv,bs,Dc) pool codes; ks/vs:
    (NB,Hkv,bs,Dh//g) f32 pool scales; block_table: (B,nblk) int32 physical
    block ids; cur_pos: (B,) int32 → o (B,H,1,Dh).

    The S-tile is one pool block (``bs = block_size``): grid (B, Hkv, nblk)
    with the block axis sequential, the block table riding as a
    scalar-prefetch argument so each tile's BlockSpec resolves its physical
    pool block before the body runs (the paged flash-decoding idiom)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, _, Dh = q.shape
    Hkv, bs = kq.shape[1], kq.shape[2]
    G = H // Hkv
    Gn = ks.shape[3]
    Dc = kq.shape[3]
    nblk = block_table.shape[1]
    sc = scale if scale is not None else Dh ** -0.5
    qg = (q[:, :, 0].astype(jnp.float32) * sc).astype(
        q.dtype).reshape(B, Hkv, G, Dh)
    bt = jnp.asarray(block_table, jnp.int32)
    pos = jnp.asarray(cur_pos, jnp.int32)

    kern = functools.partial(_paged_attn_kernel, bits=bits,
                             group_size=group_size, soft_cap=soft_cap,
                             bs=bs, Dh=Dh, n_s=nblk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table + cur_pos
        grid=(B, Hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, bt_r, p_r: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dc),
                         lambda b, h, s, bt_r, p_r: (bt_r[b, s], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Gn),
                         lambda b, h, s, bt_r, p_r: (bt_r[b, s], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dc),
                         lambda b, h, s, bt_r, p_r: (bt_r[b, s], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Gn),
                         lambda b, h, s, bt_r, p_r: (bt_r[b, s], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, s, bt_r, p_r: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),       # running max
            pltpu.VMEM((G, 1), jnp.float32),       # running denom
            pltpu.VMEM((G, Dh), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bt, pos, qg, kq, ks, vq, vs)
    return out.reshape(B, H, 1, Dh).astype(q.dtype)


def _pad_seq(x, m):
    r = (-x.shape[2]) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, r)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "scale",
                                             "soft_cap", "bs", "interpret"))
def ttq_decode_attention(q: jnp.ndarray, kq: jnp.ndarray, ks: jnp.ndarray,
                         vq: jnp.ndarray, vs: jnp.ndarray, cur_pos: jnp.ndarray,
                         *, bits: int = 8, group_size: int = 0,
                         scale: float | None = None, soft_cap: float = 0.0,
                         bs: int = 256, interpret: bool | None = None
                         ) -> jnp.ndarray:
    """q: (B,H,1,Dh); kq/vq: (B,Hkv,S,Dc) codes; ks/vs: (B,Hkv,S,Dh//g) f32;
    cur_pos: (B,) int32 → o (B,H,1,Dh).  Positions > cur_pos are masked."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, _, Dh = q.shape
    Hkv, S = kq.shape[1], kq.shape[2]
    G = H // Hkv
    Gn = ks.shape[3]
    Dc = kq.shape[3]
    sc = scale if scale is not None else Dh ** -0.5
    qg = (q[:, :, 0].astype(jnp.float32) * sc).astype(
        q.dtype).reshape(B, Hkv, G, Dh)

    bs = min(bs, S)
    kq, ks = _pad_seq(kq, bs), _pad_seq(ks, bs)
    vq, vs = _pad_seq(vq, bs), _pad_seq(vs, bs)
    Sp = kq.shape[2]
    n_s = Sp // bs
    pos = jnp.asarray(cur_pos, jnp.int32)

    kern = functools.partial(_attn_kernel, bits=bits, group_size=group_size,
                             soft_cap=soft_cap, bs=bs, Dh=Dh, n_s=n_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # cur_pos
        grid=(B, Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, p_r: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dc), lambda b, h, s, p_r: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, Gn), lambda b, h, s, p_r: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, Dc), lambda b, h, s, p_r: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, Gn), lambda b, h, s, p_r: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, s, p_r: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),       # running max
            pltpu.VMEM((G, 1), jnp.float32),       # running denom
            pltpu.VMEM((G, Dh), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos, qg, kq, ks, vq, vs)
    return out.reshape(B, H, 1, Dh).astype(q.dtype)
