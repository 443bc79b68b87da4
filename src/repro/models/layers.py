"""Layer components — mixers (attn / MLA / RG-LRU / SSD) and MLPs (dense / MoE).

Every component exposes:
  init_<kind>(key, cfg)                       → param dict
  <kind>_apply(cfg, p, x, stats, prefix, ...) → sequence-mode output (train/prefill)
  <kind>_decode(cfg, p, x, state, pos, ...)   → (y, new_state) single-token
  <kind>_init_state(cfg, batch, max_len)      → decode-state ShapeDtype/zeros

Stats taps use param-path-aligned names (``prefix + "attn.wq"``) so the TTQ
quantizer can join stats ↔ weights by path (see core/ttq.quantize_tree).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import (ACT, Array, attention, cache_update, cache_update_batched,
                     decode_attention, glu_mlp, init_glu_mlp, init_linear,
                     init_norm, init_plain_mlp, linear, norm, plain_mlp,
                     rmsnorm, rope_decode, seq_update_batched, apply_rope,
                     suffix_attention)
from .config import ModelConfig

DTYPE = jnp.bfloat16


# ===========================================================================
# GQA/MQA attention (dense, vlm, hybrid-attn, encdec self/cross)
# ===========================================================================

def init_attn(key, cfg: ModelConfig, cross: bool = False):
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], H * hd, D),
        "wk": init_linear(ks[1], Hkv * hd, D),
        "wv": init_linear(ks[2], Hkv * hd, D),
        "wo": init_linear(ks[3], D, H * hd),
    }
    if cfg.qk_norm:
        p["qnorm"] = init_norm(hd)
        p["knorm"] = init_norm(hd)
    return p


def _qkv(cfg: ModelConfig, p, xq: Array, xkv: Array, stats, prefix: str,
         kcfg=None, pctx=None):
    B = xq.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(xq, p["wq"], stats, prefix + "wq", kcfg, pctx=pctx,
               tp="row").reshape(B, -1, H, hd)
    k = linear(xkv, p["wk"], None, kcfg=kcfg, pctx=pctx,
               tp="row").reshape(B, -1, Hkv, hd)
    v = linear(xkv, p["wv"], None, kcfg=kcfg, pctx=pctx,
               tp="row").reshape(B, -1, Hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qnorm"]["gamma"])
        k = rmsnorm(k, p["knorm"]["gamma"])
    # (B, H, S, hd)
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def attn_apply(cfg: ModelConfig, p, x: Array, stats, prefix: str, *,
               causal: bool = True, window: int = 0, pos0: int = 0,
               x_cross: Optional[Array] = None, return_kv: bool = False,
               kv_prefix=None, kvcfg=None, kcfg=None):
    """Sequence-mode attention. x: (B,S,D). Cross-attn if x_cross given.

    ``kv_prefix`` = (k, v) each (B, Hkv, P, Dh): already-cached context
    (post-rope, e.g. a shared prompt prefix gathered from the paged pool)
    prepended to this call's keys/values; the queries then start at absolute
    position ``pos0 == P`` and the causal mask offsets accordingly (tail
    prefill for prefix-cache hits — DESIGN.md §8).  ``return_kv`` returns
    only the *new* k/v (the prefix is already cached).

    With a *quantized* ``kvcfg`` (prefill contexts only) the attention read
    runs over the quantize→dequantize of k/v — exactly the values the cache
    will hold and every later decode step will read.  This keeps a
    preemption-resumed re-prefill on the same numbers the evicted slot's
    decode saw, so the greedy stream continues identically."""
    xkv = x_cross if x_cross is not None else x
    q, k, v = _qkv(cfg, p, x, xkv, stats, prefix, kcfg)
    S = x.shape[1]
    pos = jnp.arange(S) + pos0
    if cfg.pos == "rope" and x_cross is None:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, jnp.arange(k.shape[2]) + pos0, cfg.rope_theta)
    kf, vf = k, v
    if kvcfg is not None and kvcfg.quantized and x_cross is None:
        from repro.core.kvquant import dequantize_kv, quantize_kv
        kf, vf = (dequantize_kv(*quantize_kv(t, bits=kvcfg.bits,
                                             group_size=kvcfg.group_size),
                                jnp.float32, bits=kvcfg.bits,
                                group_size=kvcfg.group_size) for t in (k, v))
    q_off = 0
    if kv_prefix is not None:
        pk, pv = kv_prefix
        kf = jnp.concatenate([pk.astype(kf.dtype), kf], axis=2)
        vf = jnp.concatenate([pv.astype(vf.dtype), vf], axis=2)
        q_off = pk.shape[2]
    o = attention(q, kf, vf, causal=causal and x_cross is None, window=window,
                  soft_cap=cfg.attn_soft_cap, q_offset=q_off)
    y = linear(o.transpose(0, 2, 1, 3).reshape(x.shape[0], S, -1), p["wo"],
               stats, prefix + "wo", kcfg)
    if return_kv:
        return y, (k, v)
    return y


def attn_init_state(cfg: ModelConfig, batch: int, max_len: int, kvcfg=None,
                    num_blocks: int = 0):
    """Decode-state cache for one attention layer.

    bf16 (kvcfg None / dtype='bf16'): {'k','v'} (B,Hkv,Smax,Dh) — the seed
    layout.  Quantized: {'k_q','k_s','v_q','v_s'} with int8 / packed-int4
    codes plus f32 per-(head, token, group) scales (DESIGN.md §"KV-cache
    layout").

    Paged (``kvcfg.paged``): the same leaf names hold a shared block *pool*
    (num_blocks, Hkv, block_size, ·) instead of per-slot slabs; per-slot
    block tables live at the decode-state top level (DESIGN.md §8).
    """
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    if kvcfg is not None and kvcfg.paged:
        lead = (num_blocks, Hkv, kvcfg.block_size)
    else:
        lead = (batch, Hkv, max_len)
    if kvcfg is None or not kvcfg.quantized:
        z = jnp.zeros((*lead, hd), DTYPE)
        return {"k": z, "v": z}
    cz = jnp.zeros((*lead, kvcfg.code_shape(hd)), kvcfg.code_dtype)
    sz = jnp.zeros((*lead, kvcfg.groups(hd)), jnp.float32)
    return {"k_q": cz, "k_s": sz, "v_q": cz, "v_s": sz}


def build_kv_state(cfg: ModelConfig, batch: int, max_len: int, k: Array,
                   v: Array, kvcfg=None):
    """Prefill write point: materialize the decode cache from sequence-mode
    k/v (B,Hkv,S,Dh), quantizing at the cache's storage dtype."""
    z = attn_init_state(cfg, batch, max_len, kvcfg)
    if kvcfg is None or not kvcfg.quantized:
        return {"k": jax.lax.dynamic_update_slice(z["k"], k.astype(DTYPE),
                                                  (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(z["v"], v.astype(DTYPE),
                                                  (0, 0, 0, 0))}
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"] = jax.lax.dynamic_update_slice(
            z[name + "_q"], codes, (0, 0, 0, 0))
        out[name + "_s"] = jax.lax.dynamic_update_slice(
            z[name + "_s"], scales, (0, 0, 0, 0))
    return out


def _kv_append(state, k: Array, v: Array, pos, kvcfg):
    """Per-decode-step append: quantize one token's k/v and write both the
    codes and the per-slot scale rows at position ``pos``."""
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"] = cache_update_batched(state[name + "_q"], codes, pos)
        out[name + "_s"] = cache_update_batched(state[name + "_s"], scales, pos)
    return out


def build_kv_compact(k: Array, v: Array, kvcfg):
    """Paged prefill write point: the prompt's k/v (B,Hkv,S,Dh) at the
    cache's storage dtype, *compact* (no max_len slab) — the runner scatters
    these rows into the slot's pool blocks (DESIGN.md §8)."""
    if kvcfg is None or not kvcfg.quantized:
        return {"k": k.astype(DTYPE), "v": v.astype(DTYPE)}
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"], out[name + "_s"] = codes, scales
    return out


def _pool_row_write(pool: Array, row: Array, phys: Array, off: Array) -> Array:
    """pool (NB,Hkv,bs,D·) ← row (B,Hkv,1,D·) at (phys (B,), off (B,)).

    A vectorized scatter: distinct live slots own distinct blocks, so the
    only duplicate index is the sink block 0 (done/empty lanes), where any
    write order is acceptable."""
    return pool.at[phys, :, off].set(row[:, :, 0].astype(pool.dtype))


def _kv_append_paged(state, k: Array, v: Array, pos, block_table, kvcfg):
    """Paged decode append: one token's k/v row lands in pool block
    ``block_table[b, pos // block_size]`` at offset ``pos % block_size``."""
    bs = kvcfg.block_size
    pos = jnp.asarray(pos, jnp.int32)
    nblk = block_table.shape[1]
    blk = jnp.clip(pos // bs, 0, nblk - 1)
    phys = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    off = pos % bs
    if not kvcfg.quantized:
        return {"k": _pool_row_write(state["k"], k, phys, off),
                "v": _pool_row_write(state["v"], v, phys, off)}
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"] = _pool_row_write(state[name + "_q"], codes, phys, off)
        out[name + "_s"] = _pool_row_write(state[name + "_s"], scales, phys, off)
    return out


def _kv_attention_paged(q: Array, state, block_table, cur, kvcfg, *,
                        soft_cap: float = 0.0, pctx=None):
    """Decode read over the paged pool.  Quantized pools go through the
    fused paged kernel (``use_pallas`` escape hatch routes to the gather
    oracle); the bf16 pool gathers its block-table view and reuses the
    dense ``decode_attention`` bit-for-bit.  With a mesh, the dispatch is
    shard_map'd over KV heads (kernels/ops.py TP wrappers)."""
    if kvcfg.quantized:
        from repro.kernels import ops as kops
        return kops.kv_paged_decode_attention_tp(
            q, state["k_q"], state["k_s"], state["v_q"], state["v_s"],
            block_table, cur, bits=kvcfg.bits, group_size=kvcfg.group_size,
            soft_cap=soft_cap, use_pallas=kvcfg.use_pallas, pctx=pctx)
    from repro.kernels.ref import gather_paged_kv
    kc = gather_paged_kv(state["k"], block_table)
    vc = gather_paged_kv(state["v"], block_table)
    return decode_attention(q, kc, vc, cur, soft_cap=soft_cap)


def _kv_attention(q: Array, state, cur, kvcfg, *, soft_cap: float = 0.0,
                  window: int = 0, pctx=None):
    """Fused dequant attention read over the quantized cache (a nonzero
    ``window`` routes to the jnp oracle, which applies the window mask)."""
    from repro.kernels import ops as kops
    return kops.kv_decode_attention_tp(
        q, state["k_q"], state["k_s"], state["v_q"], state["v_s"], cur,
        bits=kvcfg.bits, group_size=kvcfg.group_size, soft_cap=soft_cap,
        window=window, use_pallas=kvcfg.use_pallas, pctx=pctx)


def attn_decode(cfg: ModelConfig, p, x: Array, state, pos, *, window: int = 0,
                cross_kv=None, kvcfg=None, kcfg=None, block_table=None,
                pctx=None):
    """x: (B,1,D); state: bf16 {'k','v'} or quantized {'k_q','k_s','v_q',
    'v_s'} caches (``kvcfg`` selects); pos: (B,) per-slot positions.
    ``block_table`` (B, nblk) routes the paged pool layout (DESIGN.md §8).
    ``pctx``: head-parallel TP — wq/wk/wv row-split, wo column-split, and
    the quantized-cache attention reads shard over KV heads."""
    if cross_kv is not None:
        k, v = cross_kv
        B = x.shape[0]
        H, hd = cfg.n_heads, cfg.hd
        q = linear(x, p["wq"], kcfg=kcfg, pctx=pctx, tp="row").reshape(B, 1, H, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["qnorm"]["gamma"])
        q = q.transpose(0, 2, 1, 3)
        o = attention(q, k, v, causal=False, soft_cap=cfg.attn_soft_cap)
        y = linear(o.transpose(0, 2, 1, 3).reshape(B, 1, -1), p["wo"],
                   kcfg=kcfg, pctx=pctx, tp="col")
        return y, state
    q, k, v = _qkv(cfg, p, x, x, None, "", kcfg, pctx=pctx)
    if cfg.pos == "rope":
        q = rope_decode(q, pos, cfg.rope_theta)
        k = rope_decode(k, pos, cfg.rope_theta)
    if kvcfg is not None and kvcfg.paged:
        st = _kv_append_paged(state, k, v, pos, block_table, kvcfg)
        o = _kv_attention_paged(q, st, block_table, pos, kvcfg,
                                soft_cap=cfg.attn_soft_cap, pctx=pctx)
        y = linear(o.reshape(x.shape[0], 1, -1), p["wo"], kcfg=kcfg,
                   pctx=pctx, tp="col")
        return y, st
    if kvcfg is not None and kvcfg.quantized:
        st = _kv_append(state, k, v, pos, kvcfg)
        o = _kv_attention(q, st, pos, kvcfg, soft_cap=cfg.attn_soft_cap,
                          window=window, pctx=pctx)
        y = linear(o.reshape(x.shape[0], 1, -1), p["wo"], kcfg=kcfg,
                   pctx=pctx, tp="col")
        return y, st
    kc = cache_update_batched(state["k"], k, pos)
    vc = cache_update_batched(state["v"], v, pos)
    o = decode_attention(q, kc, vc, pos, window=window,
                         soft_cap=cfg.attn_soft_cap)
    y = linear(o.reshape(x.shape[0], 1, -1), p["wo"], kcfg=kcfg, pctx=pctx,
               tp="col")
    return y, {"k": kc, "v": vc}


def attn_decode_rolling(cfg: ModelConfig, p, x: Array, state, pos,
                        window: int, kvcfg=None, kcfg=None, pctx=None):
    """Windowed decode with a rolling (B,Hkv,W,hd) cache — O(W) per step.

    Slot validity needs no ordering (softmax is set-wise): slot i is valid iff
    i ≤ pos (cache fills left-to-right before wrapping). pos: (B,).
    """
    q, k, v = _qkv(cfg, p, x, x, None, "", kcfg, pctx=pctx)
    if cfg.pos == "rope":
        q = rope_decode(q, pos, cfg.rope_theta)
        k = rope_decode(k, pos, cfg.rope_theta)
    wpos = jnp.mod(pos, window)
    # validity: min(pos, W-1) marks the highest filled slot
    cur = jnp.minimum(pos, window - 1)
    if kvcfg is not None and kvcfg.quantized:
        st = _kv_append(state, k, v, wpos, kvcfg)
        o = _kv_attention(q, st, cur, kvcfg, soft_cap=cfg.attn_soft_cap,
                          pctx=pctx)
        y = linear(o.reshape(x.shape[0], 1, -1), p["wo"], kcfg=kcfg,
                   pctx=pctx, tp="col")
        return y, st
    kc = cache_update_batched(state["k"], k, wpos)
    vc = cache_update_batched(state["v"], v, wpos)
    o = decode_attention(q, kc, vc, cur, soft_cap=cfg.attn_soft_cap)
    y = linear(o.reshape(x.shape[0], 1, -1), p["wo"], kcfg=kcfg, pctx=pctx,
               tp="col")
    return y, {"k": kc, "v": vc}


def _kv_write_rows(cache: Array, new: Array, pos: Array) -> Array:
    """cache (B,Hkv,Smax,D·) ← new (B,Hkv,S,D·) at rows pos[b]..pos[b]+S-1.

    Window scatter for speculative verify (DESIGN.md §11).  Rows are NOT
    clamped: a row landing at or beyond Smax is dropped (``mode='drop'``) —
    clamping would let a later in-window write corrupt row Smax-1 before a
    still-valid query at the capacity boundary reads it."""
    B, _, S = new.shape[:3]
    rows = pos[:, None] + jnp.arange(S)[None, :]               # (B, S)
    bidx = jnp.arange(B)[:, None]
    return cache.at[bidx, :, rows].set(
        new.transpose(0, 2, 1, 3).astype(cache.dtype), mode="drop")


def _kv_append_rows(state, k: Array, v: Array, pos, kvcfg):
    """Quantized-slab window append: the whole (B,Hkv,S,Dh) drafted window's
    codes and scale rows land at positions pos..pos+S-1 (per-row math is
    identical to :func:`_kv_append`'s single-token quantize)."""
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"] = _kv_write_rows(state[name + "_q"], codes, pos)
        out[name + "_s"] = _kv_write_rows(state[name + "_s"], scales, pos)
    return out


def _pool_rows_write(pool: Array, new: Array, phys: Array, off: Array) -> Array:
    """pool (NB,Hkv,bs,D·) ← new (B,Hkv,S,D·) at (phys (B,S), off (B,S)).

    Multi-row sibling of :func:`_pool_row_write`; in-window rows of one slot
    hit distinct (block, offset) cells, so the only duplicate index is the
    sink block 0 (done lanes / over-capacity rows), where write order is
    irrelevant."""
    return pool.at[phys, :, off].set(new.transpose(0, 2, 1, 3).astype(pool.dtype))


def _kv_append_rows_paged(state, k: Array, v: Array, pos, block_table, kvcfg):
    """Paged window append: row j of the window lands in pool block
    ``block_table[b, (pos+j) // bs]`` at offset ``(pos+j) % bs``.  Rows at or
    beyond the slot's logical capacity route to the sink block 0 instead of
    clamping (same capacity rule as :func:`_kv_write_rows`)."""
    bs = kvcfg.block_size
    pos = jnp.asarray(pos, jnp.int32)
    S = k.shape[2]
    nblk = block_table.shape[1]
    rows = pos[:, None] + jnp.arange(S)[None, :]               # (B,S) absolute
    blk = jnp.clip(rows // bs, 0, nblk - 1)
    phys = jnp.take_along_axis(block_table, blk, axis=1)       # (B,S)
    phys = jnp.where(rows < nblk * bs, phys, 0)                # sink overflow
    off = rows % bs
    if not kvcfg.quantized:
        return {"k": _pool_rows_write(state["k"], k, phys, off),
                "v": _pool_rows_write(state["v"], v, phys, off)}
    from repro.core.kvquant import quantize_kv
    out = {}
    for name, t in (("k", k), ("v", v)):
        codes, scales = quantize_kv(t, bits=kvcfg.bits,
                                    group_size=kvcfg.group_size)
        out[name + "_q"] = _pool_rows_write(state[name + "_q"], codes, phys, off)
        out[name + "_s"] = _pool_rows_write(state[name + "_s"], scales, phys, off)
    return out


def attn_verify(cfg: ModelConfig, p, x: Array, state, pos, *, kvcfg=None,
                kcfg=None, block_table=None, pctx=None):
    """Speculative-verify attention: score a whole drafted window at once.

    x: (B,S,D) — the window's token embeddings at absolute positions
    ``pos[b]..pos[b]+S-1`` (pos: (B,) per-slot window starts).  Writes the
    window's k/v rows at the cache's storage dtype FIRST (overwriting the
    draft pass's rows), then runs the multi-query suffix read over the
    updated cache — write-then-read keeps the key axis identical to
    sequential decode, so greedy verify logits match ``attn_decode``
    bit-for-bit and KV rollback of rejected tokens is just a position
    rewind (DESIGN.md §11).  Returns (y (B,S,D), new_state)."""
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, x, None, "", kcfg, pctx=pctx)
    if cfg.pos == "rope":
        qpos = (pos[:, None] + jnp.arange(S))[:, None, :]      # (B,1,S)
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)
    cap = cfg.attn_soft_cap
    if kvcfg is not None and kvcfg.paged:
        from repro.kernels import ops as kops
        st = _kv_append_rows_paged(state, k, v, pos, block_table, kvcfg)
        if kvcfg.quantized:
            o = kops.kv_paged_suffix_attention_tp(
                q, st["k_q"], st["k_s"], st["v_q"], st["v_s"], block_table,
                pos, bits=kvcfg.bits, group_size=kvcfg.group_size,
                soft_cap=cap, use_pallas=kvcfg.use_pallas, pctx=pctx)
        else:
            from repro.kernels.ref import gather_paged_kv
            o = suffix_attention(q, gather_paged_kv(st["k"], block_table),
                                 gather_paged_kv(st["v"], block_table), pos,
                                 soft_cap=cap)
    elif kvcfg is not None and kvcfg.quantized:
        from repro.kernels import ops as kops
        st = _kv_append_rows(state, k, v, pos, kvcfg)
        o = kops.kv_suffix_attention_tp(
            q, st["k_q"], st["k_s"], st["v_q"], st["v_s"], pos,
            bits=kvcfg.bits, group_size=kvcfg.group_size, soft_cap=cap,
            use_pallas=kvcfg.use_pallas, pctx=pctx)
    else:
        kc = _kv_write_rows(state["k"], k, pos)
        vc = _kv_write_rows(state["v"], v, pos)
        st = {"k": kc, "v": vc}
        o = suffix_attention(q, kc, vc, pos, soft_cap=cap)
    y = linear(o.transpose(0, 2, 1, 3).reshape(B, S, -1), p["wo"], kcfg=kcfg,
               pctx=pctx, tp="col")
    return y, st


# ===========================================================================
# MLA — DeepSeek-V2 multi-head latent attention (compressed KV cache)
# ===========================================================================

def init_mla(key, cfg: ModelConfig):
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": init_linear(ks[0], H * qd, D),
        "wkv_a": init_linear(ks[1], m.kv_lora_rank + m.qk_rope_dim, D),
        "kv_norm": init_norm(m.kv_lora_rank),
        "wkv_b": init_linear(ks[2], H * (m.qk_nope_dim + m.v_head_dim), m.kv_lora_rank),
        "wo": init_linear(ks[3], D, H * m.v_head_dim),
    }


def _mla_expand(cfg, p, latent, stats=None, prefix="", kcfg=None, pctx=None):
    """latent (B,S,r) → k_nope (B,H,S,nope), v (B,H,S,vd)."""
    m, H = cfg.mla, cfg.n_heads
    kv = linear(latent, p["wkv_b"], stats, prefix + "wkv_b", kcfg, pctx=pctx,
                tp="row")
    B, S = kv.shape[0], kv.shape[1]
    kv = kv.reshape(B, S, H, m.qk_nope_dim + m.v_head_dim).transpose(0, 2, 1, 3)
    return kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]


def mla_apply(cfg: ModelConfig, p, x: Array, stats, prefix: str, *,
              pos0: int = 0, return_cache: bool = False, kcfg=None):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    qd = m.qk_nope_dim + m.qk_rope_dim
    q = linear(x, p["wq"], stats, prefix + "wq", kcfg).reshape(B, S, H, qd).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    a = linear(x, p["wkv_a"], None, kcfg=kcfg)            # shares input with wq
    latent = rmsnorm(a[..., : m.kv_lora_rank], p["kv_norm"]["gamma"])
    k_rope = a[..., m.kv_lora_rank:][:, None]             # (B,1,S,rope) shared head
    pos = jnp.arange(S) + pos0
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    k_rope = apply_rope(k_rope, pos, cfg.rope_theta)
    k_nope, v = _mla_expand(cfg, p, latent, stats, prefix, kcfg)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, H, S, m.qk_rope_dim))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attention(qf, k, v, causal=True, scale=qd ** -0.5)
    y = linear(o.transpose(0, 2, 1, 3).reshape(B, S, -1), p["wo"], stats,
               prefix + "wo", kcfg)
    if return_cache:
        return y, {"latent": latent, "k_rope": k_rope[:, 0]}
    return y


def mla_init_state(cfg: ModelConfig, batch: int, max_len: int):
    m = cfg.mla
    return {"latent": jnp.zeros((batch, max_len, m.kv_lora_rank), DTYPE),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), DTYPE)}


def mla_decode(cfg: ModelConfig, p, x: Array, state, pos, kcfg=None,
               pctx=None):
    """Decode with the compressed cache (latent+rope per token — the MLA win).

    pos: (B,) per-slot positions.
    """
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    qd = m.qk_nope_dim + m.qk_rope_dim
    q = linear(x, p["wq"], kcfg=kcfg, pctx=pctx,
               tp="row").reshape(B, 1, H, qd).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    a = linear(x, p["wkv_a"], kcfg=kcfg)
    latent_t = rmsnorm(a[..., : m.kv_lora_rank], p["kv_norm"]["gamma"])
    k_rope_t = a[..., m.kv_lora_rank:]
    q_rope = rope_decode(q_rope, pos, cfg.rope_theta)
    k_rope_t = rope_decode(k_rope_t[:, None], pos, cfg.rope_theta)[:, 0]
    latent = seq_update_batched(state["latent"], latent_t, pos)
    k_rope = seq_update_batched(state["k_rope"], k_rope_t[:, None]
                                if k_rope_t.ndim == 2 else k_rope_t, pos)
    k_nope, v = _mla_expand(cfg, p, latent, kcfg=kcfg, pctx=pctx)  # expand full cache
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (B, H, k_rope.shape[1], m.qk_rope_dim))],
        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = decode_attention(qf, k, v, pos, scale=qd ** -0.5)
    y = linear(o.reshape(B, 1, -1), p["wo"], kcfg=kcfg, pctx=pctx, tp="col")
    return y, {"latent": latent, "k_rope": k_rope}


# ===========================================================================
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ===========================================================================

_RG_BLOCKS = 16   # block-diagonal gates (Griffin §2.4) — TP-local per shard
_RG_C = 8.0


def init_rec(key, cfg: ModelConfig):
    h = cfg.hybrid
    D, dr = cfg.d_model, (h.d_rnn or cfg.d_model)
    nb = _RG_BLOCKS
    ks = jax.random.split(key, 6)
    gate = lambda k: (jax.random.normal(k, (nb, dr // nb, dr // nb), jnp.float32)
                      * (dr // nb) ** -0.5).astype(DTYPE)
    return {
        "w_branch": init_linear(ks[0], dr, D),            # gelu branch
        "w_in": init_linear(ks[1], dr, D),                # recurrent branch
        "conv_w": (jax.random.normal(ks[2], (h.conv_width, dr), jnp.float32) * 0.1).astype(DTYPE),
        "w_gate_a": gate(ks[3]),                          # recurrence gate (block-diag)
        "w_gate_x": gate(ks[4]),                          # input gate (block-diag)
        "log_lambda": jnp.log(jnp.expm1(                  # softplus⁻¹ of decay
            -jnp.log(jax.random.uniform(ks[5], (dr,), jnp.float32, 0.9, 0.999)))),
        "w_out": init_linear(jax.random.fold_in(key, 7), D, dr),
    }


def _block_diag(u: Array, w: Array) -> Array:
    """u: (B,S,dr), w: (nb, o, i) block-diagonal → (B,S,dr). TP-local on dr."""
    nb = w.shape[0]
    ub = u.reshape(*u.shape[:-1], nb, u.shape[-1] // nb)
    return jnp.einsum("bsgi,goi->bsgo", ub, w.astype(u.dtype)).reshape(u.shape)


def _rglru_coeffs(p, u: Array):
    """u: (B,S,dr) conv output → per-step (a, b) of h_t = a·h_{t-1} + b."""
    rf = jax.nn.sigmoid(_block_diag(u, p["w_gate_a"]).astype(jnp.float32))
    inp = jax.nn.sigmoid(_block_diag(u, p["w_gate_x"]).astype(jnp.float32))
    log_a = -_RG_C * jax.nn.softplus(p["log_lambda"])[None, None] * rf
    a = jnp.exp(log_a)
    gated = inp * u.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated
    return a, b


def _causal_conv(u: Array, w: Array, state: Optional[Array] = None):
    """Depthwise causal conv. u: (B,S,dr), w: (W,dr). state: (B,W-1,dr) history."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((u.shape[0], W - 1, u.shape[2]), u.dtype)
    else:
        pad = state.astype(u.dtype)
    ext = jnp.concatenate([pad, u], axis=1)
    out = sum(ext[:, i: i + u.shape[1]] * w[i][None, None] for i in range(W))
    return out, ext[:, -(W - 1):]                          # (B,S,dr), new history


def rec_apply(cfg: ModelConfig, p, x: Array, stats, prefix: str, *,
              h0: Optional[Array] = None, return_state: bool = False,
              kcfg=None):
    """Sequence mode via associative scan (O(log S) depth — SP/long-context safe)."""
    br = jax.nn.gelu(linear(x, p["w_branch"], stats, prefix + "w_branch",
                            kcfg).astype(jnp.float32))
    u = linear(x, p["w_in"], None, kcfg=kcfg)
    u, conv_state = _causal_conv(u, p["conv_w"])
    a, b = _rglru_coeffs(p, u)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def comb(l, r):
        return (r[0] * l[0], r[0] * l[1] + r[1])

    _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    y = linear((br * h).astype(x.dtype), p["w_out"], stats,
               prefix + "w_out", kcfg)
    if return_state:
        return y, {"h": h[:, -1].astype(jnp.float32), "conv": conv_state}
    return y


def rec_init_state(cfg: ModelConfig, batch: int, max_len: int):
    h = cfg.hybrid
    dr = h.d_rnn or cfg.d_model
    return {"h": jnp.zeros((batch, dr), jnp.float32),
            "conv": jnp.zeros((batch, h.conv_width - 1, dr), DTYPE)}


def rec_decode(cfg: ModelConfig, p, x: Array, state, pos, kcfg=None,
               pctx=None):
    br = jax.nn.gelu(linear(x, p["w_branch"], kcfg=kcfg, pctx=pctx,
                            tp="row").astype(jnp.float32))
    u = linear(x, p["w_in"], kcfg=kcfg, pctx=pctx, tp="row")
    u, conv_state = _causal_conv(u, p["conv_w"], state["conv"])
    a, b = _rglru_coeffs(p, u)
    h = a[:, 0] * state["h"] + b[:, 0]                     # (B, dr)
    y = linear((br[:, 0] * h)[:, None].astype(x.dtype), p["w_out"], kcfg=kcfg,
               pctx=pctx, tp="col")
    return y, {"h": h, "conv": conv_state}


# ===========================================================================
# Mamba2 SSD (state-space duality, chunked)
# ===========================================================================

def init_ssd(key, cfg: ModelConfig):
    """Projections are split (z/x/B/C/dt) so TP shards z,x on heads while the
    small shared B,C,dt stay replicated — a fused in_proj would force mixed
    sharding of one weight (DESIGN.md §4)."""
    s, D = cfg.ssm, cfg.d_model
    di = s.expand * D
    nh = di // s.head_dim
    gn = s.n_groups * s.d_state
    ks = jax.random.split(key, 8)
    return {
        "w_z": init_linear(ks[0], di, D),
        "w_x": init_linear(ks[1], di, D),
        "w_B": init_linear(ks[2], gn, D),
        "w_C": init_linear(ks[3], gn, D),
        "w_dt": init_linear(ks[4], nh, D),
        "conv_x": (jax.random.normal(ks[5], (s.conv_width, di), jnp.float32) * 0.1).astype(DTYPE),
        "conv_B": (jax.random.normal(ks[6], (s.conv_width, gn), jnp.float32) * 0.1).astype(DTYPE),
        "conv_C": (jax.random.normal(ks[7], (s.conv_width, gn), jnp.float32) * 0.1).astype(DTYPE),
        "A_log": jnp.log(jax.random.uniform(jax.random.fold_in(key, 8), (nh,), jnp.float32, 1.0, 16.0)),
        "Dskip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jax.random.uniform(jax.random.fold_in(key, 9), (nh,), jnp.float32, 1e-3, 0.1))),
        "norm": init_norm(di),
        "w_out": init_linear(jax.random.fold_in(key, 10), D, di),
    }


def _ssd_split(cfg: ModelConfig, p, x, stats, prefix, kcfg=None, pctx=None):
    """Five projections; stats tapped once on w_x (w_z/w_B/w_C/w_dt alias it)."""
    s, D = cfg.ssm, cfg.d_model
    di = s.expand * D
    nh = di // s.head_dim
    gn = s.n_groups * s.d_state
    z = linear(x, p["w_z"], None, kcfg=kcfg, pctx=pctx, tp="row")
    xr = linear(x, p["w_x"], stats, prefix + "w_x", kcfg, pctx=pctx, tp="row")
    Br = linear(x, p["w_B"], None, kcfg=kcfg)
    Cr = linear(x, p["w_C"], None, kcfg=kcfg)
    dt = linear(x, p["w_dt"], None, kcfg=kcfg)
    return z, xr, Br, Cr, dt, di, nh, gn


def _segsum(a: Array) -> Array:
    """a: (..., Q) log-decays → (..., Q, Q) lower-tri cumulative sums."""
    Q = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    diff = c[..., :, None] - c[..., None, :]
    ii, jj = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    return jnp.where(ii >= jj, diff, -jnp.inf)


def ssd_scan(xh: Array, dt: Array, A: Array, Bm: Array, Cm: Array, chunk: int,
             h0: Optional[Array] = None):
    """Chunked SSD (Mamba2 alg. 1). xh:(B,S,H,P), dt:(B,S,H), A:(H,),
    Bm/Cm:(B,S,G,N) → y:(B,S,H,P), h_last:(B,H,P,N)."""
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    nc = S // Q
    rep = H // G
    xf = xh.astype(jnp.float32) * dt[..., None]
    la = (-A[None, None] * dt)                                       # (B,S,H) log decay
    xc = xf.reshape(Bsz, nc, Q, H, P)
    lc = la.reshape(Bsz, nc, Q, H)
    Bc = jnp.repeat(Bm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N), rep, axis=3)
    Cc = jnp.repeat(Cm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N), rep, axis=3)
    cum = jnp.cumsum(lc, axis=2)                                     # (B,nc,Q,H)
    # intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(lc.transpose(0, 1, 3, 2)))                   # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Cc, Bc)
    y_diag = jnp.einsum("bchqk,bchqk,bckhp->bcqhp", scores, L,
                        xc)
    # chunk states
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)                  # (B,nc,Q,H)
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", Bc, decay_states, xc)
    # inter-chunk recurrence over chunk boundary states
    chunk_decay = jnp.exp(cum[:, :, -1, :])                          # (B,nc,H)

    def comb(l, r):
        return (r[0] * l[0], r[1] + r[0][..., None, None] * l[1])

    if h0 is not None:
        states = states.at[:, 0].add(chunk_decay[:, 0][..., None, None] * h0)
    _, run = jax.lax.associative_scan(comb, (chunk_decay, states), axis=1)
    h_last = run[:, -1]                                              # (B,H,P,N)
    prev = jnp.concatenate([jnp.zeros_like(run[:, :1]) if h0 is None
                            else h0[:, None], run[:, :-1]], axis=1)
    y_off = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp", Cc, jnp.exp(cum), prev)
    y = (y_diag + y_off).reshape(Bsz, S, H, P)
    return y, h_last


def ssd_apply(cfg: ModelConfig, p, x: Array, stats, prefix: str, *,
              state=None, return_state: bool = False, kcfg=None):
    s = cfg.ssm
    z, xr, Br, Cr, dt, di, nh, gn = _ssd_split(cfg, p, x, stats, prefix, kcfg)
    st = state or {}
    xc, cs_x = _causal_conv(xr, p["conv_x"], st.get("conv_x"))
    Bc, cs_B = _causal_conv(Br, p["conv_B"], st.get("conv_B"))
    Cc, cs_C = _causal_conv(Cr, p["conv_C"], st.get("conv_C"))
    xi = jax.nn.silu(xc.astype(jnp.float32)).reshape(*x.shape[:2], nh, s.head_dim)
    Bm = jax.nn.silu(Bc.astype(jnp.float32)).reshape(*x.shape[:2], s.n_groups, s.d_state)
    Cm = jax.nn.silu(Cc.astype(jnp.float32)).reshape(*x.shape[:2], s.n_groups, s.d_state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None])
    A = jnp.exp(p["A_log"])
    h0 = st.get("h")
    Sq = x.shape[1]
    padn = (-Sq) % min(s.chunk, max(Sq, 1))
    if padn:
        # pad with dt=0 steps: decay=1, contribution=0 → state passes through
        pad4 = [(0, 0), (0, padn), (0, 0), (0, 0)]
        y, h_last = ssd_scan(jnp.pad(xi, pad4), jnp.pad(dtv, [(0, 0), (0, padn), (0, 0)]),
                             A, jnp.pad(Bm, pad4), jnp.pad(Cm, pad4), s.chunk, h0)
        y = y[:, :Sq]
    else:
        y, h_last = ssd_scan(xi, dtv, A, Bm, Cm, s.chunk, h0)
    y = y + p["Dskip"][None, None, :, None] * xi                    # D·x skip
    y = y.reshape(*x.shape[:2], di)
    y = rmsnorm(y.astype(x.dtype), p["norm"]["gamma"]) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = linear(y, p["w_out"], stats, prefix + "w_out", kcfg)
    if return_state:
        return out, {"h": h_last, "conv_x": cs_x, "conv_B": cs_B, "conv_C": cs_C}
    return out


def ssd_init_state(cfg: ModelConfig, batch: int, max_len: int):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    nh = di // s.head_dim
    gn = s.n_groups * s.d_state
    w = s.conv_width - 1
    return {"h": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
            "conv_x": jnp.zeros((batch, w, di), DTYPE),
            "conv_B": jnp.zeros((batch, w, gn), DTYPE),
            "conv_C": jnp.zeros((batch, w, gn), DTYPE)}


def ssd_decode(cfg: ModelConfig, p, x: Array, state, pos, kcfg=None,
               pctx=None):
    """Single-step SSM recurrence h ← e^{-A·dt}h + dt·B⊗x ; y = C·h + D·x."""
    s = cfg.ssm
    z, xr, Br, Cr, dt, di, nh, gn = _ssd_split(cfg, p, x, None, "", kcfg,
                                               pctx=pctx)
    xc, cs_x = _causal_conv(xr, p["conv_x"], state["conv_x"])
    Bc, cs_B = _causal_conv(Br, p["conv_B"], state["conv_B"])
    Cc, cs_C = _causal_conv(Cr, p["conv_C"], state["conv_C"])
    B = x.shape[0]
    xi = jax.nn.silu(xc.astype(jnp.float32))[:, 0].reshape(B, nh, s.head_dim)
    Bm = jax.nn.silu(Bc.astype(jnp.float32))[:, 0].reshape(B, s.n_groups, s.d_state)
    Cm = jax.nn.silu(Cc.astype(jnp.float32))[:, 0].reshape(B, s.n_groups, s.d_state)
    rep = nh // s.n_groups
    Bm = jnp.repeat(Bm, rep, axis=1)                                # (B,H,N)
    Cm = jnp.repeat(Cm, rep, axis=1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)[:, 0] + p["dt_bias"][None])  # (B,H)
    decay = jnp.exp(-jnp.exp(p["A_log"])[None] * dtv)               # (B,H)
    h = state["h"] * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dtv, xi, Bm)
    y = jnp.einsum("bhpn,bhn->bhp", h, Cm) + p["Dskip"][None, :, None] * xi
    y = y.reshape(B, 1, di)
    y = rmsnorm(y.astype(x.dtype), p["norm"]["gamma"]) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = linear(y, p["w_out"], kcfg=kcfg, pctx=pctx, tp="col")
    return out, {"h": h, "conv_x": cs_x, "conv_B": cs_B, "conv_C": cs_C}


# ===========================================================================
# MoE MLP — dense-compute (exact, tiny tests/training) and a2a (production)
# ===========================================================================

def init_moe(key, cfg: ModelConfig):
    e, D = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 3)
    def expert_stack(k):
        kk = jax.random.split(k, e.n_experts)
        return jax.vmap(lambda kq: init_glu_mlp(kq, D, e.d_ff_expert))(kk)
    p = {"router": init_linear(ks[0], e.n_experts, D, dtype=jnp.float32),
         "experts": expert_stack(ks[1])}
    if e.n_shared:
        p["shared"] = init_glu_mlp(ks[2], D, e.d_ff_expert * e.n_shared)
    return p


def _router(cfg, p, x2, stats, prefix):
    e = cfg.moe
    logits = linear(x2.astype(jnp.float32), p["router"], stats, prefix + "router")
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, e.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i


def _expert_mm(h, w, kcfg=None):
    """Per-expert matmul: h (E,C,D) × w (E,F,D) → (E,C,F). QT-aware: the
    vmapped kernel path batches the Pallas ttq_gemm over the expert dim
    (one dispatch with a leading batch grid axis, not E dispatches)."""
    from repro.core.ttq import QuantizedTensor, ttq_matmul
    if isinstance(w, QuantizedTensor):
        return jax.vmap(lambda hh, ww: ttq_matmul(hh, ww, kcfg=kcfg))(
            h, w).astype(h.dtype)
    return jnp.einsum("ecd,efd->ecf", h, w.astype(h.dtype))


def _expert_glu(w, h, act, stats=None, prefix="", wts=None, kcfg=None):
    """w: stacked expert params {wg,wu,wd} (E,·,·); h: (E,C,D).

    ``wts`` (E,C) optionally weights the TTQ stats accumulation (dense path:
    routing mass, so unrouted tokens don't pollute the per-expert diagonal).
    """
    g = _expert_mm(h, w["wg"], kcfg)
    u = _expert_mm(h, w["wu"], kcfg)
    a = ACT[act](g.astype(jnp.float32)).astype(h.dtype) * u
    if stats is not None:
        hf, af = h.astype(jnp.float32), a.astype(jnp.float32)
        wt = jnp.ones(h.shape[:2], jnp.float32) if wts is None else wts
        stats[prefix + "experts.wg"] = stats.get(prefix + "experts.wg", 0.0) + \
            jnp.einsum("ec,ecd,ecd->ed", wt, hf, hf)
        stats[prefix + "experts.wd"] = stats.get(prefix + "experts.wd", 0.0) + \
            jnp.einsum("ec,ecf,ecf->ef", wt, af, af)
    return _expert_mm(a, w["wd"], kcfg)


def moe_apply_dense(cfg: ModelConfig, p, x: Array, stats, prefix: str,
                    kcfg=None):
    """Exact MoE: every expert computes every token, combined by gates.

    O(E/topk) extra FLOPs — for tests, training of small models, and as the
    oracle for the a2a path.  Shared experts are added by the caller.
    """
    e = cfg.moe
    B, S, D = x.shape
    x2 = x.reshape(-1, D)
    top_p, top_i = _router(cfg, p, x2, stats, prefix)
    gate = jnp.zeros((x2.shape[0], e.n_experts), jnp.float32)
    gate = jax.vmap(lambda g, i, v: g.at[i].add(v))(gate, top_i, top_p)
    h = jnp.broadcast_to(x2[None], (e.n_experts, x2.shape[0], D))
    y_all = _expert_glu(p["experts"], h, cfg.act, stats, prefix, wts=gate.T,
                        kcfg=kcfg)
    y = jnp.einsum("etd,te->td", y_all.astype(jnp.float32), gate).astype(x.dtype)
    return y.reshape(B, S, D)


def moe_a2a(cfg: ModelConfig, p, x: Array, stats_on: bool, prefix: str, pctx):
    """shard_map wrapper around :func:`moe_apply_a2a` (EP over the model axis).

    x: (B,S,D) global, batch on data axes; experts E-sharded on model.
    Returns (y, stats_dict) — stats replicated (psum'd inside).
    """
    e = cfg.moe
    mesh = pctx.mesh
    P = jax.sharding.PartitionSpec
    dp = pctx.dp
    pr = {"router": p["router"], "experts": p["experts"]}
    espec = jax.tree.map(
        lambda l: P(pctx.model_axis, *([None] * (l.ndim - 1))), pr["experts"])
    in_specs = (P(dp, None, None), {"router": P(None, None), "experts": espec})
    if stats_on:
        out_specs = (P(dp, None, None), {prefix + "experts.wg": P(None, None),
                                         prefix + "experts.wd": P(None, None)})
    else:
        out_specs = (P(dp, None, None), {})

    def fn(xx, pp):
        st = {} if stats_on else None
        y = moe_apply_a2a(cfg, pp, xx, st, prefix,
                          model_axis=pctx.model_axis, data_axes=pctx.data_axes)
        return y, (st if stats_on else {})

    y, st = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)(x, pr)
    return y, st


def moe_apply_a2a(cfg: ModelConfig, p, x: Array, stats, prefix: str, *,
                  model_axis: str, data_axes: tuple):
    """Production EP path — runs INSIDE shard_map over the full mesh.

    x: (B_loc, S, D) (replicated over `model_axis`). Experts are sharded over
    `model_axis` (leading E dim). Tokens are round-robin split over model
    ranks, dispatched to expert-owning ranks with all_to_all, processed with
    dense per-expert matmuls, and returned. Capacity-dropped tokens fall back
    to zero (standard); gates renormalized locally.
    """
    e = cfg.moe
    tp = jax.lax.axis_size(model_axis)
    my = jax.lax.axis_index(model_axis)
    B, S, D = x.shape
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    Tc = -(-T // tp)                                   # this rank's token chunk
    if Tc * tp != T:                                   # pad tokens to tp multiple
        x2 = jnp.pad(x2, ((0, Tc * tp - T), (0, 0)))
    xm = jax.lax.dynamic_slice(x2, (my * Tc, 0), (Tc, D))
    top_p, top_i = _router(cfg, p, xm, None, prefix)   # (Tc,k)
    k = e.top_k
    E = e.n_experts
    E_loc = E // tp
    C = max(1, int(Tc * k / E * e.capacity_factor))
    flat_e = top_i.reshape(-1)                         # (Tc·k,) target expert
    # position of each assignment within its target expert (stable order)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1          # (Tc·k, E)
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    valid = slot < C
    dest_rank = flat_e // E_loc
    dest_eloc = flat_e % E_loc
    flat_idx = (dest_rank * E_loc + dest_eloc) * C + jnp.where(valid, slot, 0)
    send = jnp.zeros((tp * E_loc * C, D), x2.dtype)
    src_tok = jnp.repeat(jnp.arange(Tc), k)
    send = send.at[flat_idx].add(jnp.where(valid[:, None], xm[src_tok], 0))
    send = send.reshape(tp, E_loc, C, D)
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                              tiled=False)             # (tp, E_loc, C, D)
    h = recv.transpose(1, 0, 2, 3).reshape(E_loc, tp * C, D)
    w_loc = p["experts"]                               # (E_loc, ·, ·) shard
    loc_stats = {} if stats is not None else None
    y_exp = _expert_glu(w_loc, h, cfg.act, loc_stats, prefix)  # (E_loc, tp·C, D)
    if stats is not None:
        for key, s_loc in loc_stats.items():           # (E_loc, ·) local shards
            s_all = jax.lax.all_gather(s_loc, model_axis, axis=0)
            s_all = s_all.reshape(E, s_loc.shape[-1])
            s_all = jax.lax.psum(s_all, data_axes)
            stats[key] = stats.get(key, 0.0) + s_all
    y_back = y_exp.reshape(E_loc, tp, C, D).transpose(1, 0, 2, 3)
    y_recv = jax.lax.all_to_all(y_back, model_axis, split_axis=0, concat_axis=0,
                                tiled=False)           # (tp, E_loc, C, D) at source
    y_flat = y_recv.reshape(tp * E_loc * C, D)
    contrib = y_flat[flat_idx] * jnp.where(valid, top_p.reshape(-1), 0.0)[:, None].astype(x2.dtype)
    y_m = jax.ops.segment_sum(contrib, src_tok, num_segments=Tc)
    y = jax.lax.all_gather(y_m, model_axis, axis=0).reshape(Tc * tp, D)[:T]
    return y.reshape(B, S, D).astype(x.dtype)
