"""Pallas-TPU fused dequant decode-attention — the KV-cache analogue of
``ttq_gemm``.

o (B,H,1,Dh) = softmax(q·deq(K_codes)ᵀ/√Dh) · deq(V_codes)

The cache lives in HBM as int8 codes (1 B/elem) or int4 packed 8-per-int32
(0.5 B/elem) plus f32 per-(head, token, group) scales — decode attention is
memory-bound, so moving ~half (int8) or ~quarter (int4) of the bf16 bytes is
the entire speedup mechanism (EXPERIMENTS.md §Roofline).  Per tile the
kernels:

  HBM→VMEM  k/v codes (·, Dh·bits/32 or ·, Dh) + scales (·, Dh/g)
  VPU       unpack nibbles (shift+mask, int4 only), dequantize to f32 with
            the groupwise scale broadcast — the cache is NEVER materialized
            at bf16 in HBM
  MXU       (G, Dh) @ (Dh, T) scores with q and k in q's dtype (bf16 when
            serving); online-softmax accumulate p·v (v in q's dtype, p as a
            hi/lo pair of it) into a (G, Dh) f32 output tile
            (flash-decoding over the S axis)

Positions beyond ``cur_pos`` are masked with an explicit where (NOT
exp(-inf - -inf), which would poison fully-masked tiles).

``ttq_decode_attention`` (contiguous slab): grid (B, Hkv, S/bs), the S axis
sequential, BlockSpec tiles; the running max/denominator/accumulator live
in VMEM scratch, initialized at s==0 and written out at the last tile.

``ttq_paged_decode_attention`` (block pool): grid (B,), one step per lane.
The block table and ``cur_pos`` ride in SMEM (scalar prefetch); the pools
stay in HBM and the kernel copies a lane's *live* pages itself — one copy
per page and pool holds every KV head — :func:`pages_per_tile` pages per
compute tile, double-buffered across tiles and lanes.  Pages past a lane's
position, and every page of a lane whose row starts with the sink block
(released or parked), are never copied nor computed.

Validated in interpret mode on the CPU against
``ref.kv_attn_ref`` / ``ref.kv_paged_attn_ref``; ``ops`` holds the public
wrappers with the ``use_pallas=False`` escape hatch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dequant_tile(codes, scales, *, bits: int, group_size: int, Dh: int):
    """codes (..., bs, Dc) int8/int32, scales (..., bs, Dh//g) f32 →
    (..., bs, Dh) f32."""
    if bits == 8:
        w = codes.astype(jnp.float32)
    else:
        shifts = (jnp.arange(8, dtype=jnp.int32) * 4).reshape(
            (1,) * codes.ndim + (8,))
        w = (codes[..., None] >> shifts) & 0xF                 # (..., Dh//8, 8)
        w = w.reshape(*codes.shape[:-1], Dh).astype(jnp.float32) - 8.0
    g = group_size or Dh
    s = scales.astype(jnp.float32)
    if g != Dh:
        s = jnp.repeat(s, g, axis=-1)                          # (..., bs, Dh)
    return w * s


def _scores(q, k):
    """(G, Dh) queries · (T, Dh) keys → (G, T) f32: operands in q's dtype
    (bf16 when serving: one MXU pass, the fp path's precision), f32
    accumulation."""
    return jax.lax.dot_general(q, k.astype(q.dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _weighted_values(p, v, dtype):
    """(G, T) f32 softmax weights · (T, Dh) values → (G, Dh) f32.  The
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


def _online_softmax(q, k, v, mask, m_prev, l_prev, acc_prev, soft_cap):
    """Fold one tile of keys k / values v (T, Dh) into the running max and
    denominator (G, 1) and the (G, Dh) f32 accumulator; ``mask`` (G, T)
    marks the tile's positions at or before the lane's ``cur_pos``."""
    s = _scores(q, k)                                          # (G, T)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit mask-zeroing: a fully-masked tile must contribute 0, not
    # exp(NEG_INF - NEG_INF) = 1 per slot
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)               # (G, T)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + _weighted_values(p, v, q.dtype)
    return m_new, l_new, acc_new


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
    deq = functools.partial(_dequant_tile, bits=bits, group_size=group_size,
                            Dh=Dh)
    k = deq(kq_ref[0, 0], ks_ref[0, 0])                        # (bs, Dh)
    v = deq(vq_ref[0, 0], vs_ref[0, 0])                        # (bs, Dh)
    ki = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], bs), 1)
    m_ref[...], l_ref[...], acc_ref[...] = _online_softmax(
        q, k, v, ki <= cur, m_ref[...], l_ref[...], acc_ref[...], soft_cap)

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


# ------------------------------------------------------------ paged read

SINK = 0               # physical block 0: the allocator's write sink
_TILE_TOKENS = 512     # tokens per compute tile the paged read aims at
_TILE_VMEM = 8 << 20   # VMEM bytes its double-buffered page tiles may take


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM array: the last two dims padded to the native tile
    (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit words × 128 lanes)."""
    item = jnp.dtype(dtype).itemsize
    *lead, r, c = shape
    rows = -(-r // (32 // item)) * (32 // item)
    return math.prod(lead) * rows * (-(-c // 128) * 128) * item


def _view_shape(shape):
    """Shape of :func:`_page_view` of a (NB, Hkv, bs, C) pool."""
    NB, Hkv, bs, C = shape
    if C % 128 == 0:
        return shape
    return (NB, 1, bs, -(-Hkv * C // 128) * 128)


def _page_view(pool):
    """A (NB, Hkv, bs, C) pool as pages one DMA each can copy: the pool
    itself when C fills whole 128-lane rows (int8 codes), else (NB, 1, bs,
    W) with every head's C values of a token side by side on one row (head
    h at lanes h·C), padded to 128 lanes (scales, int4 codes).  The
    re-laid copy holds Hkv·C of every 128 lanes; the pool stays as it is."""
    NB, Hkv, bs, C = pool.shape
    if C % 128 == 0:
        return pool
    rows = pool.transpose(0, 2, 1, 3).reshape(NB, 1, bs, Hkv * C)
    pad = _view_shape(pool.shape)[3] - Hkv * C
    return jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, pad)))


def pages_per_tile(pools, nblk: int, Dh: int) -> int:
    """Pool pages per compute tile of the paged read, from the shapes of
    its four pools (k, v codes and scales): about ``_TILE_TOKENS`` tokens,
    at most ``nblk``, and no more than fit ``_TILE_VMEM`` as two buffers of
    every pool's page view plus one head's f32 dequant temporaries; a power
    of two, so a tile's positions fill whole 128-lane rows."""
    bs = pools[0].shape[2]
    page = sum(2 * _vmem_bytes(_view_shape(a.shape)[1:], a.dtype)
               for a in pools)
    page += 4 * bs * Dh * 4
    p = max(1, min(nblk, _TILE_TOKENS // bs, _TILE_VMEM // page))
    return 1 << (p.bit_length() - 1)


def _head(buf, slot, page, h: int, C: int):
    """Head h's (bs, C) codes or scales of tile page ``page`` (or (P, bs,
    C) of every page for ``page = slice(None)``) in ``slot`` of a page-view
    buffer (2, P, ·, bs, ·), laid out as :func:`_page_view` lays it."""
    if C % 128 == 0:
        return buf[slot, page, h]
    return buf[slot, page, 0][..., h * C:(h + 1) * C]


def _paged_attn_kernel(bt_ref, pos_ref, q_ref, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
                       o_ref, kq_buf, ks_buf, vq_buf, vs_buf, sem, m_ref,
                       l_ref, acc_ref, k_tile, v_tile, *, bits: int,
                       group_size: int, soft_cap: float, bs: int, Dh: int,
                       P: int, nblk: int, Dc: int, Gn: int):
    """Flash-decoding of lane ``b = program_id(0)`` over its live pages.

    The page views stay in HBM; each live page ``bt[b, j]`` is one
    contiguous slab per view, every KV head at once, copied into slot
    ``g % 2`` of the VMEM tile buffers, where ``g`` counts the tiles of all
    lanes in order.  While tile g computes, tile g + 1 (the lane's next, or
    the first of the next lane with live pages) is in flight.  Pages past
    the lane's live count are neither copied nor read; positions past
    ``cur_pos`` inside the last tile are masked."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    Hkv, G = q_ref.shape[1], q_ref.shape[2]
    T = P * bs

    def live_pages(lane):
        n = jnp.minimum(pos_ref[lane] // bs + 1, nblk)
        return jnp.where(bt_ref[lane, 0] == SINK, 0, n)

    def tiles(lane):
        return (live_pages(lane) + P - 1) // P

    def page_copies(lane, t, slot, act):
        def one(i, c):
            page = bt_ref[lane, t * P + i]
            for hbm, buf in ((kq_hbm, kq_buf), (ks_hbm, ks_buf),
                             (vq_hbm, vq_buf), (vs_hbm, vs_buf)):
                getattr(pltpu.make_async_copy(hbm.at[page], buf.at[slot, i],
                                              sem.at[slot]), act)()
            return c
        jax.lax.fori_loop(0, jnp.minimum(P, live_pages(lane) - t * P), one, 0)

    n_t = tiles(b)
    first = jax.lax.fori_loop(0, b, lambda i, c: c + tiles(i), 0)

    @pl.when((n_t > 0) & (first == 0))
    def _prime():                       # the first lane with live pages
        page_copies(b, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    cur = pos_ref[b]
    deq = functools.partial(_dequant_tile, bits=bits, group_size=group_size,
                            Dh=Dh)
    # int4 nibbles and group scales widen along lanes: dequantize those a
    # page at a time (a whole tile of them takes minutes to compile)
    by_page = bits != 8 or Gn not in (1, Dh)

    def head_tile(codes, scales, h, dst, t, slot):
        """Head h's dequantized (T, Dh) f32 keys or values of tile t."""
        if not by_page:
            return deq(_head(codes, slot, slice(None), h, Dc),
                       _head(scales, slot, slice(None), h, Gn)).reshape(T, Dh)

        def one(i, c):
            dst[pl.ds(pl.multiple_of(i * bs, bs), bs), :] = deq(
                _head(codes, slot, i, h, Dc), _head(scales, slot, i, h, Gn))
            return c
        jax.lax.fori_loop(0, jnp.minimum(P, live_pages(b) - t * P), one, 0)
        return dst[...]

    def tile(t, c):
        slot = (first + t) % 2

        @pl.when(t + 1 < n_t)
        def _next_tile():
            page_copies(b, t + 1, 1 - slot, "start")

        @pl.when(t + 1 == n_t)
        def _next_lane():
            nxt = jax.lax.fori_loop(
                b + 1, B, lambda i, c: jnp.where((c == B) & (tiles(i) > 0),
                                                 i, c), B)

            @pl.when(nxt < B)
            def _():
                page_copies(nxt, 0, 1 - slot, "start")

        page_copies(b, t, slot, "wait")
        ki = t * T + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
        rows = t * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        for h in range(Hkv):
            k = head_tile(kq_buf, ks_buf, h, k_tile, t, slot)
            # rows past the live pages hold whatever the buffer held: zero
            # them so their (zero) weights cannot meet a non-finite value
            v = jnp.where(rows <= cur,
                          head_tile(vq_buf, vs_buf, h, v_tile, t, slot), 0.0)
            m_ref[h], l_ref[h], acc_ref[h] = _online_softmax(
                q_ref[0, h], k, v, ki <= cur, m_ref[h], l_ref[h], acc_ref[h],
                soft_cap)
        return c

    jax.lax.fori_loop(0, n_t, tile, 0)
    # a lane with no live page writes acc 0 / 1e-30 = 0
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


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

    Grid (B,), sequential: lane b reads only its live pages,
    ``min(cur_pos[b] // bs + 1, nblk)`` of them, or none when its row
    starts with the sink block (a released or parked lane: output 0).  A
    compute tile is :func:`pages_per_tile` pages × every KV head, copied
    page by page from HBM through the scalar-prefetched block table and
    double-buffered across tiles and lanes."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, _, Dh = q.shape
    _, Hkv, bs, Dc = kq.shape
    G = H // Hkv
    Gn = ks.shape[3]
    nblk = block_table.shape[1]
    P = pages_per_tile((kq, ks, vq, vs), nblk, Dh)
    views = [_page_view(a) for a in (kq, ks, vq, vs)]
    sc = scale if scale is not None else Dh ** -0.5
    qg = (q[:, :, 0].astype(jnp.float32) * sc).astype(
        q.dtype).reshape(B, Hkv, G, Dh)
    bt = jnp.asarray(block_table, jnp.int32)
    pos = jnp.asarray(cur_pos, jnp.int32)

    kern = functools.partial(_paged_attn_kernel, bits=bits,
                             group_size=group_size, soft_cap=soft_cap,
                             bs=bs, Dh=Dh, P=P, nblk=nblk, Dc=Dc, Gn=Gn)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    lane = pl.BlockSpec((1, Hkv, G, Dh), lambda b, bt_r, p_r: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table + cur_pos
        grid=(B,),
        in_specs=[lane, hbm, hbm, hbm, hbm],
        out_specs=lane,
        scratch_shapes=[pltpu.VMEM((2, P) + v.shape[1:], v.dtype)
                        for v in views] + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running denom
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),     # output accumulator
            pltpu.VMEM((P * bs, Dh), jnp.float32),     # one head's keys
            pltpu.VMEM((P * bs, Dh), jnp.float32),     # and values
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(bt, pos, qg, *views)
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
