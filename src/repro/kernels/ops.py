"""jit'd public wrappers for the Pallas kernels, with pure-jnp fallbacks.

The rest of the framework calls these; ``use_pallas=False`` (or unsupported
bit-widths) routes to the XLA fallback so every code path runs everywhere.

The ``*_tp`` variants wrap a dispatch in ``shard_map`` when a mesh is active
so each device runs the kernel on its local weight/KV-head shard
(DESIGN.md §"Mesh-sharded serving"); when the static shapes don't divide the
model axis they fall back to the unwrapped call, which GSPMD partitions.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref as _ref
from .ttq_attn import ttq_decode_attention as _ttq_attn_pallas
from .ttq_attn import ttq_paged_decode_attention as _ttq_paged_attn_pallas
from .ttq_gemm import ttq_gemm as _ttq_gemm_pallas
from .ttq_quantize import ttq_quantize as _ttq_quantize_pallas

_PACKABLE = (2, 4, 8)
_KV_BITS = (4, 8)


def ttq_gemm(x, packed, scale, zero, dinv=None, *, bits=4, group_size=32,
             use_pallas=True, out_dtype=None, **block_kw):
    if use_pallas and bits in _PACKABLE:
        return _ttq_gemm_pallas(x, packed, scale, zero, dinv, bits=bits,
                                group_size=group_size, out_dtype=out_dtype,
                                **block_kw)
    lead = x.shape[:-1]
    y = _ref.ttq_gemm_ref(x.reshape(-1, x.shape[-1]), packed, scale, zero,
                          bits=bits, group_size=group_size, dinv=dinv)
    return y.reshape(*lead, -1).astype(out_dtype or x.dtype)


def kv_decode_attention(q, kq, ks, vq, vs, cur_pos, *, bits=8, group_size=0,
                        scale=None, soft_cap=0.0, window=0, use_pallas=True,
                        **block_kw):
    """Decode attention over an int8/int4 KV cache (fused dequant read).

    The Pallas path streams the quantized cache HBM→VMEM and dequantizes
    in-register; unsupported bit-widths or a windowed mask route to the
    pure-jnp oracle so every code path runs everywhere.
    """
    if use_pallas and bits in _KV_BITS and window == 0:
        return _ttq_attn_pallas(q, kq, ks, vq, vs, cur_pos, bits=bits,
                                group_size=group_size, scale=scale,
                                soft_cap=soft_cap, **block_kw)
    return _ref.kv_attn_ref(q, kq, ks, vq, vs, cur_pos, bits=bits,
                            group_size=group_size, scale=scale,
                            soft_cap=soft_cap, window=window)


def kv_paged_decode_attention(q, kq, ks, vq, vs, block_table, cur_pos, *,
                              bits=8, group_size=0, scale=None, soft_cap=0.0,
                              use_pallas=True):
    """Decode attention over a block-paged int8/int4 KV pool.

    ``kq/ks/vq/vs`` are the (NB, Hkv, block_size, ·) pools; ``block_table``
    (B, nblk) maps each slot's logical blocks to physical pool blocks.  The
    Pallas path reads only each slot's live blocks (none for a slot parked
    on the sink block), many blocks and every KV head per compute tile,
    copied from HBM through the scalar-prefetched table; the fallback
    gathers the table's view and runs the contiguous jnp oracle (identical
    math on the live slots).
    """
    if use_pallas and bits in _KV_BITS:
        return _ttq_paged_attn_pallas(q, kq, ks, vq, vs, block_table, cur_pos,
                                      bits=bits, group_size=group_size,
                                      scale=scale, soft_cap=soft_cap)
    return _ref.kv_paged_attn_ref(q, kq, ks, vq, vs, block_table, cur_pos,
                                  bits=bits, group_size=group_size,
                                  scale=scale, soft_cap=soft_cap)


def kv_suffix_attention(q, kq, ks, vq, vs, pos, *, bits=8, group_size=0,
                        scale=None, soft_cap=0.0, use_pallas=True,
                        **block_kw):
    """Speculative-verify attention over an int8/int4 KV cache.

    ``q`` carries the S in-window queries per slot; the window's k/v rows
    were already scattered into the cache (write-then-read, DESIGN.md §11).
    Dispatch hint only for now: a Pallas suffix kernel would need a q-tile
    axis on the decode kernel's S-loop, so every bit-width routes to the
    pure-jnp oracle (``use_pallas`` accepted for signature parity).
    """
    del use_pallas, block_kw
    return _ref.kv_suffix_attn_ref(q, kq, ks, vq, vs, pos, bits=bits,
                                   group_size=group_size, scale=scale,
                                   soft_cap=soft_cap)


def kv_paged_suffix_attention(q, kq, ks, vq, vs, block_table, pos, *, bits=8,
                              group_size=0, scale=None, soft_cap=0.0,
                              use_pallas=True):
    """Speculative-verify attention over a block-paged int8/int4 KV pool.

    Gathers the block table's view and runs the contiguous suffix oracle —
    identical math to the paged decode read (no Pallas suffix kernel yet).
    """
    del use_pallas
    return _ref.kv_paged_suffix_attn_ref(q, kq, ks, vq, vs, block_table, pos,
                                         bits=bits, group_size=group_size,
                                         scale=scale, soft_cap=soft_cap)


def ttq_quantize(W, D, *, bits=4, group_size=32, use_pallas=True, **block_kw):
    if use_pallas and bits in _PACKABLE:
        return _ttq_quantize_pallas(W, D, bits=bits, group_size=group_size,
                                    **block_kw)
    return _ref.ttq_quantize_ref(W, D, bits=bits, group_size=group_size)


# ---------------------------------------------------------------- TP wrappers

def _mesh_sizes(pctx):
    """(model size, data size) — 0 when no usable mesh/model axis."""
    if pctx is None or pctx.mesh is None:
        return 0, 1
    sizes = dict(pctx.mesh.shape)
    n = sizes.get(pctx.model_axis, 0)
    ndp = 1
    for a in pctx.data_axes:
        ndp *= sizes.get(a, 1)
    return n, ndp


def _tp_gemm_ok(pctx, tp, x, packed, scale, bits, group_size):
    """Static-shape eligibility for a shard_map'd TP gemm: every sharded dim
    must divide exactly, and a column (input-feature) split must keep each
    local slice group- and pack-aligned so scale/zero/packed slices line up."""
    if tp not in ("row", "col") or x.ndim < 2:
        return False
    n, ndp = _mesh_sizes(pctx)
    if n <= 1 or x.shape[0] % ndp:
        return False
    if tp == "row":
        return packed.shape[1] % n == 0 and scale.shape[1] % n == 0
    d = x.shape[-1]
    per = 32 // bits
    g = group_size or d
    return (d % n == 0 and (d // n) % g == 0 and (d // n) % per == 0
            and packed.shape[0] % n == 0 and scale.shape[0] % n == 0)


def ttq_gemm_tp(x, packed, scale, zero, dinv=None, *,  # tracecheck: ok[TC303]
                bits=4, group_size=32, use_pallas=True, pctx=None, tp=None,
                **block_kw):  # use_pallas forwards to ttq_gemm's own oracle
    """``ttq_gemm`` with Megatron-style tensor parallelism.

    ``tp='row'``: output features sharded on the model axis — each device
    multiplies against its (d, d'/n) K-major shard, no collective, output
    stays sharded.  ``tp='col'``: input features sharded — each device
    consumes its x shard against a (d/n, d') weight slice and a psum over
    the model axis rebuilds the full output.  Ineligible shapes use the
    unwrapped dispatch (GSPMD partitions or replicates it).
    """
    gemm = partial(ttq_gemm, bits=bits, group_size=group_size,
                   use_pallas=use_pallas, **block_kw)
    if not _tp_gemm_ok(pctx, tp, x, packed, scale, bits, group_size):
        return gemm(x, packed, scale, zero, dinv)
    P = jax.sharding.PartitionSpec
    m, dp = pctx.model_axis, pctx.dp
    lead = [None] * (x.ndim - 2)
    if dinv is None:
        dinv = jnp.ones((x.shape[-1],), jnp.float32)
    if tp == "row":
        in_specs = (P(dp, *lead, None), P(None, m), P(None, m), P(None, m),
                    P(None))
        out_specs = P(dp, *lead, m)

        def fn(xx, pk, sc, zr, dv):
            return gemm(xx, pk, sc, zr, dv)
    else:
        in_specs = (P(dp, *lead, m), P(m, None), P(m, None), P(m, None), P(m))
        out_specs = P(dp, *lead, None)

        def fn(xx, pk, sc, zr, dv):
            # f32 partial products (the kernel accumulates in f32 anyway),
            # summed across shards before the cast — as one device would
            y = gemm(xx, pk, sc, zr, dv, out_dtype=jnp.float32)
            return jax.lax.psum(y, m).astype(xx.dtype)
    return jax.shard_map(fn, mesh=pctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        x, packed, scale, zero, dinv)


def tp_quantize_ok(pctx, tp, W, *, bits, group_size):
    """Whether :func:`ttq_quantize_tp` can run the kernel for a stacked
    (N, d', d) family on ``pctx``'s mesh: a row family needs d' to divide the
    model axis; a column family needs d to, with every local slice group-
    and pack-aligned so the K-major scale/zero/code rows split with it;
    a replicated family (``tp=None``) always can.  Expert (EP) stacks
    cannot: their leading sharded dim is merged into N."""
    n, _ = _mesh_sizes(pctx)
    if n <= 1:
        return True
    dp, d = W.shape[-2:]
    if tp == "row":
        return dp % n == 0
    if tp == "col":
        return (d % n == 0 and (d // n) % group_size == 0
                and (d // n) % (32 // bits) == 0)
    return tp is None


def ttq_quantize_tp(W, D, *, bits=4, group_size=32, pctx=None, tp=None,
                    **block_kw):
    """The Pallas ``ttq_quantize`` over a stacked (N, d', d) family with
    D (N, d) → K-major (packed (N, d·bits/32, d'), S, Z (N, d/g, d')).

    On a mesh (a Mosaic kernel cannot be partitioned by GSPMD) the call is
    shard_map'd over the family's own weight layout (``parallel/rules.py``):
    ``tp='row'`` quantizes each device's d'/n output rows, whose codes stay
    d'-sharded; ``tp='col'`` quantizes each device's d/n input columns with
    their slice of D, whose code/scale rows stay d-sharded; ``tp=None``
    (replicated) quantizes the whole stack on every device.  The caller
    checks :func:`tp_quantize_ok` first."""
    quant = jax.vmap(partial(ttq_quantize, bits=bits, group_size=group_size,
                             **block_kw))
    n, _ = _mesh_sizes(pctx)
    if n <= 1:
        return quant(W, D)
    if not tp_quantize_ok(pctx, tp, W, bits=bits, group_size=group_size):
        raise ValueError(f"{W.shape} family cannot be quantized shard-"
                         f"locally as tp={tp!r}")
    P = jax.sharding.PartitionSpec
    m = pctx.model_axis
    w_spec, d_spec, out_spec = {
        "row": (P(None, m, None), P(None, None), P(None, None, m)),
        "col": (P(None, None, m), P(None, m), P(None, m, None)),
        None: (P(), P(), P()),
    }[tp]
    return jax.shard_map(quant, mesh=pctx.mesh, in_specs=(w_spec, d_spec),
                         out_specs=(out_spec,) * 3, check_vma=False)(W, D)


def _tp_attn_ok(pctx, q, kq, batched_cache):
    n, ndp = _mesh_sizes(pctx)
    if n <= 1 or q.shape[0] % ndp:
        return False
    hkv = kq.shape[1]
    return q.shape[1] % n == 0 and hkv % n == 0


def kv_decode_attention_tp(q, kq, ks, vq, vs, cur_pos, *, pctx=None, **kw):
    """Head-parallel ``kv_decode_attention``: q heads and KV heads shard the
    model axis together (the GQA q→kv mapping is block-contiguous, so each
    device's q-head shard attends exactly its local KV-head shard)."""
    call = partial(kv_decode_attention, **kw)
    if not _tp_attn_ok(pctx, q, kq, True):
        return call(q, kq, ks, vq, vs, cur_pos)
    P = jax.sharding.PartitionSpec
    m, dp = pctx.model_axis, pctx.dp
    hs = P(dp, m, None, None)
    return jax.shard_map(lambda *a: call(*a), mesh=pctx.mesh,
                         in_specs=(hs, hs, hs, hs, hs, P(dp)), out_specs=hs,
                         check_vma=False)(q, kq, ks, vq, vs, cur_pos)


def kv_suffix_attention_tp(q, kq, ks, vq, vs, pos, *, pctx=None, **kw):
    """Head-parallel :func:`kv_suffix_attention` — same sharding contract as
    :func:`kv_decode_attention_tp` (q/KV heads co-shard the model axis; the
    per-slot window-start positions replicate per data shard)."""
    call = partial(kv_suffix_attention, **kw)
    if not _tp_attn_ok(pctx, q, kq, True):
        return call(q, kq, ks, vq, vs, pos)
    P = jax.sharding.PartitionSpec
    m, dp = pctx.model_axis, pctx.dp
    hs = P(dp, m, None, None)
    return jax.shard_map(lambda *a: call(*a), mesh=pctx.mesh,
                         in_specs=(hs, hs, hs, hs, hs, P(dp)), out_specs=hs,
                         check_vma=False)(q, kq, ks, vq, vs, pos)


def kv_paged_suffix_attention_tp(q, kq, ks, vq, vs, block_table, pos, *,
                                 pctx=None, **kw):
    """Head-parallel paged suffix attention: pools shard over KV heads, the
    block table and window-start positions replicate per data shard (mirrors
    :func:`kv_paged_decode_attention_tp`)."""
    call = partial(kv_paged_suffix_attention, **kw)
    if not _tp_attn_ok(pctx, q, kq, False):
        return call(q, kq, ks, vq, vs, block_table, pos)
    P = jax.sharding.PartitionSpec
    m, dp = pctx.model_axis, pctx.dp
    qs = P(dp, m, None, None)
    pool = P(None, m, None, None)
    return jax.shard_map(lambda *a: call(*a), mesh=pctx.mesh,
                         in_specs=(qs, pool, pool, pool, pool, P(dp, None),
                                   P(dp)),
                         out_specs=qs, check_vma=False)(
        q, kq, ks, vq, vs, block_table, pos)


def kv_paged_decode_attention_tp(q, kq, ks, vq, vs, block_table, cur_pos, *,
                                 pctx=None, **kw):
    """Head-parallel paged decode attention: the (NB, Hkv, bs, ·) pools shard
    over KV heads (never the physical-block dim — block ids are global), the
    per-slot block table and positions stay replicated per data shard."""
    call = partial(kv_paged_decode_attention, **kw)
    if not _tp_attn_ok(pctx, q, kq, False):
        return call(q, kq, ks, vq, vs, block_table, cur_pos)
    P = jax.sharding.PartitionSpec
    m, dp = pctx.model_axis, pctx.dp
    qs = P(dp, m, None, None)
    pool = P(None, m, None, None)
    return jax.shard_map(lambda *a: call(*a), mesh=pctx.mesh,
                         in_specs=(qs, pool, pool, pool, pool, P(dp, None),
                                   P(dp)),
                         out_specs=qs, check_vma=False)(
        q, kq, ks, vq, vs, block_table, cur_pos)
