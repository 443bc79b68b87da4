"""Shared model building blocks — norms, RoPE, attention, MLPs, stats taps.

Conventions
-----------
* All linear weights are (out_features, in_features); matmuls go through
  :func:`linear` which dispatches on plain arrays vs ``QuantizedTensor`` and
  optionally taps the TTQ activation statistic (Σ_t x_t² per input feature).
* Activations are bf16 by default; normalization/softmax/rope run in f32.
* ``stats`` is a flat dict {projection_name: (d_in,) f32}; inside a layer scan
  the dict becomes a scan output so leaves stack to (L, d_in).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.ttq import QuantizedTensor, ttq_matmul

Array = jnp.ndarray
ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


# ---------------------------------------------------------------------------
# linear + stats tap
# ---------------------------------------------------------------------------

def linear(x: Array, w, stats: Optional[dict] = None, name: str = "",
           kcfg=None, pctx=None, tp=None) -> Array:
    """y = x @ wᵀ (w: (out,in) array or QuantizedTensor). Taps Σx² if stats dict given.

    ``kcfg`` (:class:`~repro.core.policy.KernelConfig`) selects the Pallas
    ``ttq_gemm`` path for packed QuantizedTensors (None → jnp fallback).
    ``pctx``/``tp`` ('row'|'col') shard_map the kernel dispatch over the
    model axis; fp weights ignore both (GSPMD shards the einsum)."""
    if stats is not None:
        xf = x.astype(jnp.float32)
        s = jnp.sum(xf * xf, axis=tuple(range(x.ndim - 1)))
        stats[name] = stats.get(name, 0.0) + s
    if isinstance(w, QuantizedTensor):
        return ttq_matmul(x, w, kcfg=kcfg, pctx=pctx, tp=tp).astype(x.dtype)
    # f32 result, then the cast: a contraction GSPMD splits over the model
    # axis (column-parallel wo/wd/w2) then reduces its partial sums in f32,
    # as the single-device dot does, instead of rounding each to bf16 first
    return jnp.einsum("...d,od->...o", x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def init_linear(key, d_out: int, d_in: int, dtype=jnp.bfloat16, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_out, d_in), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: Array, gamma: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    nx = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (nx * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: Array, gamma: Array, beta: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    nx = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (nx * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def norm(x: Array, p: dict) -> Array:
    return layernorm(x, p["gamma"], p["beta"]) if "beta" in p else rmsnorm(x, p["gamma"])


def init_norm(d: int, kind: str = "rms"):
    if kind == "rms":
        return {"gamma": jnp.zeros((d,), jnp.float32)}
    return {"gamma": jnp.ones((d,), jnp.float32), "beta": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: Array, pos: Array, theta: float = 10000.0) -> Array:
    """x: (..., S, Dh); pos: (S,) or (..., S) absolute positions."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (Dh/2,)
    ang = pos[..., :, None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : dh // 2], xf[..., dh // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_decode(x: Array, pos: Array, theta: float = 10000.0) -> Array:
    """Single-token RoPE with per-batch positions. x: (B,H,1,Dh), pos: (B,)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)
    ang = pos.astype(jnp.float32)[:, None, None, None] * freqs  # (B,1,1,Dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : dh // 2], xf[..., dh // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def cache_update_batched(cache: Array, new: Array, pos: Array) -> Array:
    """cache (B,Hkv,Smax,Dh) ← new (B,Hkv,1,Dh) at per-batch seq position pos (B,)."""
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, p, 0))
    )(cache, new, pos)


def seq_update_batched(cache: Array, new: Array, pos: Array) -> Array:
    """cache (B,Smax,D) ← new (B,1,D) at per-batch position pos (B,)."""
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (p, 0))
    )(cache, new, pos)


def sinusoidal_pos(n: int, d: int) -> Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# attention — full (masked) / chunked (online-softmax) / decode (cache)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def opt_level() -> int:
    """Perf-iteration switch (EXPERIMENTS.md §Perf).

    0 — baseline: GQA expands KV to H heads, attention math materializes f32.
    1 — optimized (default): grouped-query einsums read the KV cache once at
        its storage dtype; dots accumulate f32 via preferred_element_type.
    """
    import os
    return int(os.environ.get("REPRO_OPT_LEVEL", "1"))


def _expand_kv(k: Array, H: int) -> Array:
    """GQA: (B,Hkv,S,Dh) → (B,H,S,Dh). Keeping the einsum head dim equal to
    q's head dim lets TP shard all attention intermediates on `model` without
    GSPMD reshards (the (Hkv,G) grouped form breaks when Hkv < tp)."""
    Hkv = k.shape[1]
    if Hkv == H:
        return k
    return jnp.repeat(k, H // Hkv, axis=1)


def full_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                   window: int = 0, q_offset: int = 0, scale: float | None = None,
                   soft_cap: float = 0.0) -> Array:
    """q: (B,H,S,Dh), k/v: (B,Hkv,Sk,Dh) → (B,H,S,Dh_v). Masks built from indices."""
    B, H, S, Dh = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else Dh ** -0.5
    qi = jnp.arange(S) + q_offset
    ki = jnp.arange(Sk)
    mask = jnp.ones((S, Sk), bool)
    if causal:
        mask &= qi[:, None] >= ki[None, :]
    if window > 0:
        mask &= qi[:, None] - ki[None, :] < window
    if opt_level() >= 1:
        Hkv = k.shape[1]
        G = H // Hkv
        qg = (q.astype(jnp.float32) * scale).astype(k.dtype)
        qg = qg.reshape(B, Hkv, G, S, Dh)
        s = jnp.einsum("bhgsd,bhkd->bhgsk", qg, k,
                       preferred_element_type=jnp.float32)
        if soft_cap > 0:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgsk,bhkd->bhgsd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H, S, -1).astype(q.dtype)
    kf = _expand_kv(k, H).astype(jnp.float32)
    vf = _expand_kv(v, H).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhkd->bhsk", q.astype(jnp.float32) * scale, kf)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhsk,bhkd->bhsd", p, vf)
    return o.astype(q.dtype)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      window: int = 0, kv_chunk: int = 1024,
                      scale: float | None = None, soft_cap: float = 0.0) -> Array:
    """Online-softmax attention, O(S·chunk) live memory — used for long context.

    Scans over KV chunks carrying (running-max, denom, accum); numerically
    identical to :func:`full_attention` up to fp error.
    """
    B, H, S, Dh = q.shape
    Sk = k.shape[2]
    if Sk % kv_chunk:
        raise ValueError(f"Sk={Sk} must divide by kv_chunk={kv_chunk}")
    scale = scale if scale is not None else Dh ** -0.5
    qf = q.astype(jnp.float32) * scale
    nck = Sk // kv_chunk
    Hkv = k.shape[1]
    kc = k.reshape(B, Hkv, nck, kv_chunk, Dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nck, kv_chunk, v.shape[-1]).transpose(2, 0, 1, 3, 4)
    qi = jnp.arange(S)

    grouped = opt_level() >= 1
    G = H // Hkv
    if grouped:
        qf = qf.astype(k.dtype).reshape(B, Hkv, G, S, Dh)

    def step(carry, xs):
        m, l, acc = carry
        ci, kci, vci = xs
        ki = ci * kv_chunk + jnp.arange(kv_chunk)
        mask = jnp.ones((S, kv_chunk), bool)
        if causal:
            mask &= qi[:, None] >= ki[None, :]
        if window > 0:
            mask &= qi[:, None] - ki[None, :] < window
        if grouped:
            s = jnp.einsum("bhgsd,bhkd->bhgsk", qf, kci,
                           preferred_element_type=jnp.float32)
            if soft_cap > 0:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        else:
            kcf = _expand_kv(kci, H).astype(jnp.float32)
            s = jnp.einsum("bhsd,bhkd->bhsk", qf, kcf)
            if soft_cap > 0:
                s = soft_cap * jnp.tanh(s / soft_cap)
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        if grouped:
            pv = jnp.einsum("bhgsk,bhkd->bhgsd", p.astype(vci.dtype), vci,
                            preferred_element_type=jnp.float32)
        else:
            vcf = _expand_kv(vci, H).astype(jnp.float32)
            pv = jnp.einsum("bhsk,bhkd->bhsd", p, vcf)
        acc = acc * alpha[..., None] + pv
        return (m_new, l, acc), None

    hshape = (B, Hkv, G, S) if grouped else (B, H, S)
    m0 = jnp.full(hshape, NEG_INF, jnp.float32)
    l0 = jnp.zeros(hshape, jnp.float32)
    a0 = jnp.zeros((*hshape, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (jnp.arange(nck), kc, vc))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    if grouped:
        o = o.reshape(B, H, S, -1)
    return o.astype(q.dtype)


def attention(q, k, v, *, causal=True, window=0, scale=None, soft_cap=0.0,
              q_offset: int = 0, chunk_threshold: int = 8192,
              kv_chunk: int = 1024):
    """Dispatch full vs chunked by KV length (chunked for long context).

    A nonzero ``q_offset`` (queries starting mid-context: tail prefill over
    a cached prefix) routes to the full path — the chunked scan's masks
    assume query position 0."""
    if (q_offset == 0 and k.shape[2] > chunk_threshold
            and k.shape[2] % kv_chunk == 0):
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 kv_chunk=kv_chunk, scale=scale, soft_cap=soft_cap)
    return full_attention(q, k, v, causal=causal, window=window, scale=scale,
                          soft_cap=soft_cap, q_offset=q_offset)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, cur_pos: Array,
                     *, window: int = 0, scale: float | None = None,
                     soft_cap: float = 0.0) -> Array:
    """Single-token attention over a (B,Hkv,Smax,Dh) cache; positions > cur_pos masked.

    q: (B,H,1,Dh) → (B,H,1,Dh_v).  f32 softmax, memory-bound (the decode roofline).

    Optimized path (opt_level ≥ 1): grouped-query einsum — the cache is read
    ONCE at bf16 (no G× head expansion, no f32 materialization); both dots
    accumulate in f32 (preferred_element_type).  §Perf iteration 1.
    """
    B, H, _, Dh = q.shape
    Smax = k_cache.shape[2]
    scale = scale if scale is not None else Dh ** -0.5
    ki = jnp.arange(Smax)
    mask = ki[None, :] <= cur_pos[:, None]                     # (B, Smax)
    if window > 0:
        mask &= ki[None, :] > cur_pos[:, None] - window
    if opt_level() >= 1:
        Hkv = k_cache.shape[1]
        G = H // Hkv
        qg = (q[:, :, 0].astype(jnp.float32) * scale).astype(k_cache.dtype)
        qg = qg.reshape(B, Hkv, G, Dh)
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                       preferred_element_type=jnp.float32)
        if soft_cap > 0:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H, -1)[:, :, None].astype(q.dtype)
    kf = _expand_kv(k_cache, H).astype(jnp.float32)
    vf = _expand_kv(v_cache, H).astype(jnp.float32)
    qf = q[:, :, 0].astype(jnp.float32) * scale                # (B,H,Dh)
    s = jnp.einsum("bhd,bhkd->bhk", qf, kf)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bhkd->bhd", p, vf)
    return o[:, :, None].astype(q.dtype)


def suffix_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array,
                     *, scale: float | None = None,
                     soft_cap: float = 0.0) -> Array:
    """Multi-query decode attention for a speculated window (DESIGN.md §11).

    q: (B,H,S,Dh) — S in-window queries per slot at absolute positions
    ``pos[b]..pos[b]+S-1`` over a (B,Hkv,Smax,Dh) cache whose window rows
    were just written (write-then-read).  Query s attends rows ≤ pos[b]+s.
    Key-axis layout, masking, and einsum/dtype discipline mirror
    :func:`decode_attention` exactly so a verify pass over the window
    reproduces sequential decode logits bit-for-bit.
    """
    B, H, S, Dh = q.shape
    Smax = k_cache.shape[2]
    scale = scale if scale is not None else Dh ** -0.5
    ki = jnp.arange(Smax)
    qi = pos[:, None] + jnp.arange(S)                          # (B, S)
    mask = ki[None, None, :] <= qi[:, :, None]                 # (B, S, Smax)
    if opt_level() >= 1:
        Hkv = k_cache.shape[1]
        G = H // Hkv
        qg = (q.astype(jnp.float32) * scale).astype(k_cache.dtype)
        qg = qg.reshape(B, Hkv, G, S, Dh)
        s = jnp.einsum("bhgsd,bhkd->bhgsk", qg, k_cache,
                       preferred_element_type=jnp.float32)
        if soft_cap > 0:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgsk,bhkd->bhgsd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H, S, -1).astype(q.dtype)
    kf = _expand_kv(k_cache, H).astype(jnp.float32)
    vf = _expand_kv(v_cache, H).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhkd->bhsk", q.astype(jnp.float32) * scale, kf)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhsk,bhkd->bhsd", p, vf)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def glu_mlp(x, p, stats=None, prefix="mlp", act="silu", kcfg=None, pctx=None):
    """Gated MLP (SwiGLU/GeGLU): (act(x@Wg) * (x@Wu)) @ Wd."""
    g = linear(x, p["wg"], stats, f"{prefix}.wg", kcfg, pctx=pctx, tp="row")
    u = linear(x, p["wu"], None, kcfg=kcfg, pctx=pctx,
               tp="row")  # same input stats as wg — tap once
    h = ACT[act](g.astype(jnp.float32)).astype(x.dtype) * u
    return linear(h, p["wd"], stats, f"{prefix}.wd", kcfg, pctx=pctx, tp="col")


def plain_mlp(x, p, stats=None, prefix="mlp", act="gelu", kcfg=None, pctx=None):
    h = linear(x, p["w1"], stats, f"{prefix}.w1", kcfg, pctx=pctx, tp="row")
    h = ACT[act](h.astype(jnp.float32)).astype(x.dtype)
    return linear(h, p["w2"], stats, f"{prefix}.w2", kcfg, pctx=pctx, tp="col")


def init_glu_mlp(key, d: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wg": init_linear(k1, d_ff, d, dtype),
            "wu": init_linear(k2, d_ff, d, dtype),
            "wd": init_linear(k3, d, d_ff, dtype)}


def init_plain_mlp(key, d: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2 = jax.random.split(key, 2)
    return {"w1": init_linear(k1, d_ff, d, dtype),
            "w2": init_linear(k2, d, d_ff, dtype)}


# ---------------------------------------------------------------------------
# cache helpers
# ---------------------------------------------------------------------------

def cache_update(cache: Array, new: Array, pos: Array) -> Array:
    """cache (B, Hkv, Smax, Dh) ← new (B, Hkv, 1, Dh) at seq position pos (scalar)."""
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                        (0, 0, pos, 0))


def vocab_logits(x: Array, w_head, stats=None) -> Array:
    """LM head in f32 accumulation (w: (V, D))."""
    return linear(x, w_head, stats, "lm_head").astype(jnp.float32)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def sample_logits(logits: Array, key=None, temperature: float = 0.0,
                  top_k: int = 0) -> Array:
    """logits (B, V) → (B,) int32. temperature 0 → greedy.

    Lives here (not in ``repro.serving``) so the on-device decode loop
    (``lm.decode_many``) can sample inside its scan; ``serving.sampling``
    re-exports it as the public ``sample``.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(lg, top_k)
        lg = jnp.where(lg < vals[..., -1:], -jnp.inf, lg)
    return jax.random.categorical(key, lg).astype(jnp.int32)
