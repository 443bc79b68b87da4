"""Pure-jnp oracles for the Pallas kernels (the allclose targets in tests)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp



@partial(jax.jit, static_argnames=("bits", "group_size"))
def ttq_gemm_ref(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                 zero: jnp.ndarray, *, bits: int, group_size: int,
                 dinv: jnp.ndarray | None = None) -> jnp.ndarray:
    """y (T, d') = x (T, d) [∘dinv] @ deq(packed (d·bits/32, d'),
    S, Z (d/g, d')).  The kernel's operand contract: x∘dinv and the
    dequantized weight rounded to x's dtype, products accumulated in f32 —
    here at full f32 precision, so only the accumulation order differs.
    Jitted, so the dequantization compiles as the kernel's does (a fused
    multiply-add or not decides the last f32 bit, and with it, rarely, a
    bf16 step of one weight)."""
    from repro.core.ttq import dequantize_kmajor, unpack_weight
    d = x.shape[-1]
    W = dequantize_kmajor(unpack_weight(packed, d, bits), scale, zero,
                          group_size)                               # (d, d')
    xf = x.astype(jnp.float32)
    if dinv is not None:
        xf = xf * dinv[None, :].astype(jnp.float32)
    return jnp.dot(_round(xf, x.dtype), _round(W, x.dtype),
                   precision=jax.lax.Precision.HIGHEST)


def _round(a: jnp.ndarray, dtype) -> jnp.ndarray:
    """``a`` rounded to ``dtype``, held in f32 (an MXU operand, exactly)."""
    return a.astype(dtype).astype(jnp.float32)


NEG_INF = -1e30


def _kv_operands(kq, ks, vq, vs, bits, group_size, dtype):
    """Dequantized k, v (B, Hkv, S, Dh), rounded to ``dtype`` (held in
    f32)."""
    from repro.core.kvquant import dequantize_kv
    return tuple(_round(dequantize_kv(c, sc, jnp.float32, bits=bits,
                                      group_size=group_size), dtype)
                 for c, sc in ((kq, ks), (vq, vs)))


def kv_attn_ref(q: jnp.ndarray, kq: jnp.ndarray, ks: jnp.ndarray,
                vq: jnp.ndarray, vs: jnp.ndarray, cur_pos: jnp.ndarray, *,
                bits: int = 8, group_size: int = 0,
                scale: float | None = None, soft_cap: float = 0.0,
                window: int = 0) -> jnp.ndarray:
    """Decode attention over a quantized cache: dequantize, then the same
    grouped-query math as ``models.common.decode_attention`` (f32 softmax).
    The scaled q and the dequantized k/v are rounded to q's dtype, as the
    kernel feeds them to the MXU; the softmax weights stay f32.

    q: (B,H,1,Dh); kq/vq codes (B,Hkv,S,Dc); ks/vs scales (B,Hkv,S,Dh//g);
    cur_pos: (B,) int32.  The allclose target for ``ttq_attn``.
    """
    B, H, _, Dh = q.shape
    Hkv, S = kq.shape[1], kq.shape[2]
    G = H // Hkv
    sc = scale if scale is not None else Dh ** -0.5
    k, v = _kv_operands(kq, ks, vq, vs, bits, group_size, q.dtype)
    qg = _round(q[:, :, 0].astype(jnp.float32) * sc,
                q.dtype).reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    ki = jnp.arange(S)
    mask = ki[None, :] <= cur_pos[:, None]
    if window > 0:
        mask &= ki[None, :] > cur_pos[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v)
    return o.reshape(B, H, 1, Dh).astype(q.dtype)


def kv_suffix_attn_ref(q: jnp.ndarray, kq: jnp.ndarray, ks: jnp.ndarray,
                       vq: jnp.ndarray, vs: jnp.ndarray, pos: jnp.ndarray, *,
                       bits: int = 8, group_size: int = 0,
                       scale: float | None = None,
                       soft_cap: float = 0.0) -> jnp.ndarray:
    """Speculative-window attention over a quantized cache (DESIGN.md §11).

    q: (B,H,S,Dh) — S in-window queries per slot at absolute positions
    ``pos[b]..pos[b]+S-1``; the window's k/v rows were already written to the
    cache (write-then-read), so query s attends rows ≤ pos[b]+s.  Same
    dequantize-then-grouped-query math as :func:`kv_attn_ref` with a query
    axis, so verify logits match sequential decode bit-for-bit.
    """
    B, H, S, Dh = q.shape
    Hkv, Smax = kq.shape[1], kq.shape[2]
    G = H // Hkv
    sc = scale if scale is not None else Dh ** -0.5
    k, v = _kv_operands(kq, ks, vq, vs, bits, group_size, q.dtype)
    qg = _round(q.astype(jnp.float32) * sc, q.dtype).reshape(B, Hkv, G, S, Dh)
    s = jnp.einsum("bhgsd,bhkd->bhgsk", qg, k)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    ki = jnp.arange(Smax)
    qi = pos[:, None] + jnp.arange(S)                          # (B, S)
    mask = ki[None, None, :] <= qi[:, :, None]                 # (B, S, Smax)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgsk,bhkd->bhgsd", p, v)
    return o.reshape(B, H, S, Dh).astype(q.dtype)


def kv_paged_suffix_attn_ref(q: jnp.ndarray, kq: jnp.ndarray, ks: jnp.ndarray,
                             vq: jnp.ndarray, vs: jnp.ndarray,
                             block_table: jnp.ndarray, pos: jnp.ndarray, *,
                             bits: int = 8, group_size: int = 0,
                             scale: float | None = None,
                             soft_cap: float = 0.0) -> jnp.ndarray:
    """Paged speculative-window attention: gather each slot's block-table view
    into the contiguous layout, then the exact :func:`kv_suffix_attn_ref`
    math (mirrors :func:`kv_paged_attn_ref`)."""
    kqg, ksg = gather_paged_kv(kq, block_table), gather_paged_kv(ks, block_table)
    vqg, vsg = gather_paged_kv(vq, block_table), gather_paged_kv(vs, block_table)
    return kv_suffix_attn_ref(q, kqg, ksg, vqg, vsg, pos, bits=bits,
                              group_size=group_size, scale=scale,
                              soft_cap=soft_cap)


def gather_paged_kv(pool: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a per-slot contiguous view of a paged pool.

    pool (NB, Hkv, bs, D·) indexed by block_table (B, nblk) →
    (B, Hkv, nblk·bs, D·).  Slots' unallocated entries point at the sink
    block 0; its rows are garbage but land beyond ``cur_pos`` and are masked
    by the attention read.
    """
    g = jnp.take(pool, block_table, axis=0)              # (B, nblk, Hkv, bs, D)
    B, nblk, Hkv, bs, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nblk * bs, D)


def kv_paged_attn_ref(q: jnp.ndarray, kq: jnp.ndarray, ks: jnp.ndarray,
                      vq: jnp.ndarray, vs: jnp.ndarray,
                      block_table: jnp.ndarray, cur_pos: jnp.ndarray, *,
                      bits: int = 8, group_size: int = 0,
                      scale: float | None = None,
                      soft_cap: float = 0.0) -> jnp.ndarray:
    """Paged decode attention oracle: gather the block table's view of each
    (NB, Hkv, bs, ·) pool into the contiguous (B, Hkv, S, ·) layout, then the
    exact :func:`kv_attn_ref` math — the allclose target for the paged Pallas
    kernel and the ``use_pallas=False`` fallback."""
    kqg, ksg = gather_paged_kv(kq, block_table), gather_paged_kv(ks, block_table)
    vqg, vsg = gather_paged_kv(vq, block_table), gather_paged_kv(vs, block_table)
    return kv_attn_ref(q, kqg, ksg, vqg, vsg, cur_pos, bits=bits,
                       group_size=group_size, scale=scale, soft_cap=soft_cap)


def ttq_quantize_ref(W: jnp.ndarray, D: jnp.ndarray, *, bits: int,
                     group_size: int):
    """Online scaled groupwise quantize+pack, K-major outputs.

    W (d', d), D (d,) → packed (d·bits/32, d') int32, S (d/g, d') f32,
    Z (d/g, d') f32 (the :class:`~repro.core.ttq.QuantizedTensor` layout).
    """
    from repro.core.ttq import pack_weight
    qmax = (1 << bits) - 1
    g = group_size
    dp, d = W.shape
    Ws = W.astype(jnp.float32) * D[None, :].astype(jnp.float32)
    Wg = Ws.reshape(dp, d // g, g)
    wmax = Wg.max(axis=-1)
    wmin = Wg.min(axis=-1)
    S = jnp.maximum((wmax - wmin) / qmax, 1e-12)
    Z = wmin
    wint = jnp.clip(jnp.round((Wg - Z[..., None]) / S[..., None]), 0, qmax)
    return pack_weight(wint.reshape(dp, d), bits), S.T, Z.T
