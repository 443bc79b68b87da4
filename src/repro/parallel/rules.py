"""Logical sharding rules — param-path patterns → PartitionSpec.

Megatron-style TP on the ``model`` axis, EP for MoE experts, replication for
small tensors; decode-state sharding for serving. Rules are matched on the
flattened param path (joined with '.'), first match wins.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp

from .ctx import ParallelCtx

P = jax.sharding.PartitionSpec

# (regex on path, spec builder(ndim, model_axis) -> PartitionSpec)
_RULES = [
    # embeddings / head: vocab-parallel
    (r"(^|\.)embed$",        lambda m: P(m, None)),
    (r"(^|\.)lm_head$",      lambda m: P(m, None)),
    (r"(^|\.)pos_embed$",    lambda m: P(None, None)),
    # attention — heads on model
    (r"\.(mix|xattn)\.(wq|wk|wv)$",  lambda m: P(m, None)),
    (r"\.(mix|xattn)\.wo$",          lambda m: P(None, m)),
    (r"\.mix\.(qnorm|knorm)\.",      lambda m: P(None)),
    # MLA
    (r"\.mix\.wkv_a$",       lambda m: P(None, None)),
    (r"\.mix\.wkv_b$",       lambda m: P(m, None)),
    # RG-LRU / SSD — recurrent width on model
    (r"\.mix\.(w_branch|w_in|w_z|w_x)$", lambda m: P(m, None)),
    (r"\.mix\.(w_out)$",     lambda m: P(None, m)),
    (r"\.mix\.w_gate_[ax]$", lambda m: P(m, None, None)),   # block-diag blocks
    (r"\.mix\.conv_[wxBC]$", lambda m: P(None, None)),
    (r"\.mix\.(w_B|w_C|w_dt)$", lambda m: P(None, None)),
    (r"\.mix\.(A_log|Dskip|dt_bias|log_lambda)$", lambda m: P(None)),
    # dense MLP — hidden on model
    (r"\.mlp\.(wg|wu|w1)$",  lambda m: P(m, None)),
    (r"\.mlp\.(wd|w2)$",     lambda m: P(None, m)),
    # MoE — experts on model (EP); shared expert TP'd like dense MLP
    (r"\.mlp\.experts\.(wg|wu|wd)$", lambda m: P(m, None, None)),
    (r"\.mlp\.router$",      lambda m: P(None, None)),
    (r"\.mlp\.shared\.(wg|wu)$", lambda m: P(m, None)),
    (r"\.mlp\.shared\.wd$",  lambda m: P(None, m)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def spec_for_path(path_str: str, leaf_ndim: int, model_axis: str = "model",
                  stacked: bool = True) -> P:
    """Sharding spec for one param. ``stacked``: leading layer-repeat dim."""
    for pat, builder in _RULES:
        if re.search(pat, path_str):
            spec = builder(model_axis)
            base = len(spec)
            if stacked and leaf_ndim == base + 1:
                return P(None, *spec)
            if leaf_ndim == base:
                return spec
            # pad/trim to rank
            if leaf_ndim > base:
                return P(*([None] * (leaf_ndim - base)), *spec)
            return P(*list(spec)[:leaf_ndim])
    return P(*([None] * leaf_ndim))                     # replicate by default


def tp_role(path_str: str, model_axis: str = "model") -> Optional[str]:
    """Tensor-parallel role of the 2-D (d', d) weight at ``path_str``:
    'row' (output features on the model axis), 'col' (input features) or
    None (replicated) — the ``tp`` hint of ``kernels.ops``' TP wrappers."""
    row, col = (list(spec_for_path(path_str, 2, model_axis, stacked=False))
                + [None, None])[:2]
    return "row" if row == model_axis else "col" if col == model_axis else None


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, (tuple, list)):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axes]


def divisible_spec(spec: P, shape, mesh) -> P:
    """Drop spec axes that don't divide the corresponding dim (e.g. MQA's
    single KV head can't shard over 16-way model) — GSPMD-legal everywhere."""
    out = []
    for i, ax in enumerate(spec):
        n = _axis_size(mesh, ax)
        out.append(ax if (n > 1 and shape[i] % n == 0) or n == 1 else None)
    out += [None] * (len(shape) - len(out))
    return P(*out)


# QuantizedTensor children order: (wint, packed, scale, zero, dinv, B, A)
_QT_FIELDS = ("wint", "packed", "scale", "zero", "dinv", "B", "A")


def _qt_child_specs(base: P, model_axis: str):
    """Derive per-child specs for a QuantizedTensor from its 2-D weight spec.

    base = (row, col) of the (d', d) fp weight.  wint/packed/scale/zero are
    stored K-major — (d, d'), (d/per, d'), (d/g, d') — so they take the
    transposed spec (col, row); dinv lives on the input dim (col); B on
    rows, A on cols.
    """
    row, col = (list(base) + [None, None])[:2]
    return {
        "wint": P(col, row), "packed": P(col, row), "scale": P(col, row),
        "zero": P(col, row), "dinv": P(col), "B": P(row, None), "A": P(None, col),
    }


def qt_specs(path_str: str, shapes, model_axis: str = "model", mesh=None):
    """Per-child PartitionSpecs for a QuantizedTensor at ``path_str``.

    ``shapes``: dict child-name → shape (or None for absent children, e.g.
    wint after packing, B/A without low-rank).  Pure spec logic — ``mesh``
    only needs a ``.shape`` mapping for the divisibility fallback, so
    property tests can drive this without real devices.
    """
    lead = 1 if ("stack" in path_str) else 0
    ref = shapes.get("wint") or shapes.get("packed")
    extra = len(ref) - 2 - lead              # e.g. expert dim
    base = spec_for_path(path_str, 2, model_axis, stacked=False)
    child = _qt_child_specs(base, model_axis)
    # experts: leading expert dim sharded on model (EP) → override TP
    if extra > 0:
        lead_spec = [None] * lead + [model_axis] + [None] * (extra - 1)
        child = {k: P(*lead_spec, None, None) if k != "dinv"
                 else P(*lead_spec, None) for k in child}
    else:
        lead_spec = [None] * lead
        child = {k: P(*lead_spec, *v) for k, v in child.items()}
    if mesh is not None:
        child = {k: (divisible_spec(v, shapes[k], mesh) if shapes.get(k)
                     else v) for k, v in child.items()}
    return child


def qt_sharding(path_str: str, qt, pctx: ParallelCtx):
    """QuantizedTensor of NamedShardings (None for absent children) for the
    packed tensor at ``path_str`` — the public per-tensor entry used by the
    shard-local requant path (quant/api.py) and ``param_sharding``."""
    from repro.core.ttq import QuantizedTensor
    shapes = {n: (getattr(qt, n).shape if getattr(qt, n) is not None else None)
              for n in _QT_FIELDS}
    child = qt_specs(path_str, shapes, pctx.model_axis, pctx.mesh)
    vals = [jax.sharding.NamedSharding(pctx.mesh, child[n])
            if shapes[n] is not None else None for n in _QT_FIELDS]
    return QuantizedTensor(*vals, qt.bits, qt.group_size,
                           qt.out_features, qt.in_features)


def constrain_qt(path_str: str, qt, pctx: ParallelCtx):
    """``with_sharding_constraint`` on every child of ``qt`` (trace-time use:
    pins requant outputs to the serving layout so each weight shard is
    quantized in place, never gathered)."""
    from repro.core.ttq import QuantizedTensor
    sh = qt_sharding(path_str, qt, pctx)
    vals = [jax.lax.with_sharding_constraint(getattr(qt, n), getattr(sh, n))
            if getattr(qt, n) is not None else None for n in _QT_FIELDS]
    return QuantizedTensor(*vals, qt.bits, qt.group_size,
                           qt.out_features, qt.in_features)


def param_sharding(params, pctx: ParallelCtx):
    """Pytree of NamedSharding matching ``params`` (layer-scanned leaves get a
    leading replicated dim; QuantizedTensor nodes get per-child derived specs;
    non-divisible dims fall back to replication)."""
    from repro.core.ttq import QuantizedTensor
    mesh = pctx.mesh

    def per_leaf(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            return qt_sharding(_path_str(path), leaf, pctx)
        ps = _path_str(path)
        in_stack = "stack" in ps
        spec = spec_for_path(ps, leaf.ndim, pctx.model_axis, stacked=in_stack)
        spec = divisible_spec(spec, leaf.shape, mesh)
        return jax.sharding.NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(
        per_leaf, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def shard_params(params, pctx: ParallelCtx):
    shardings = param_sharding(params, pctx)
    return jax.tree.map(jax.device_put, params, shardings)


def state_sharding(state, pctx: ParallelCtx, batch_axes=None, seq_axis=None,
                   paged: bool = False):
    """Decode/KV state: batch dim on data axes, head/width dims on model.

    Heuristic on rank: (B, Hkv, S, hd)→(dp, m, None|seq, None);
    (B, S, r)→(dp, None|seq, None); (B, dr)→(dp, m); (B, H, p, n)→(dp, m, None, None);
    (B, W, ch)→(dp, None, m); leading run-stacked dims get None.
    ``seq_axis``: shard the KV sequence dim (long-context, batch ≤ data size).
    ``paged``: KV leaves are slot-free block pools (NB, Hkv, bs, ·) — shard
    the KV-head dim only (never the block-pool dim: the block allocator's
    physical indices are global), per-slot block tables stay replicated.
    """
    mesh, m = pctx.mesh, pctx.model_axis
    dp = pctx.dp if batch_axes is None else batch_axes

    def per_leaf(path, leaf):
        ps = _path_str(path)
        nd = leaf.ndim
        lead = 1 if re.match(r"stack\.\d+\.", ps) or ".u" in ps else 0
        core = nd - lead
        if "enc_out" in ps:
            spec = P(dp, None, None)
        elif paged and re.search(r"\.(k|v)(_q|_s)?$", ps) and core == 4:
            # pool (NB, Hkv, bs, hd|groups): KV heads on model; no data axis
            # (every device addresses the full pool by physical block id)
            spec = P(None, m, None, None)
        elif re.search(r"\.(k|v|xk|xv)(_q|_s)?$", ps) and core == 4:
            # GQA w/ Hkv < tp: heads can't shard over model — fall back to
            # sharding the cache sequence dim (flash-decoding style; the
            # grouped attention einsum turns it into tiny psum/pmax combines).
            # §Perf iteration 2.  Baseline (opt 0) replicates instead.
            from repro.models.common import opt_level
            hkv = leaf.shape[lead + 1]
            msize = _axis_size(mesh, m)
            if hkv % msize == 0 or opt_level() < 1:
                spec = P(dp, m, seq_axis, None)
            else:
                spec = P(dp, None, m if seq_axis is None else seq_axis, None)
        elif re.search(r"\.(latent|k_rope)$", ps) and core == 3:
            spec = P(dp, seq_axis, None)
        elif re.search(r"\.h$", ps) and core == 2:
            spec = P(dp, m)
        elif re.search(r"\.h$", ps) and core == 4:
            spec = P(dp, m, None, None)
        elif re.search(r"\.conv", ps) and core == 3:
            spec = P(dp, None, m)
        else:
            spec = P(*([None] * core))
        if lead:
            spec = P(None, *spec)
        spec = divisible_spec(spec, leaf.shape, mesh)
        return jax.sharding.NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(per_leaf, state)
