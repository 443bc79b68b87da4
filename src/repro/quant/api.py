"""Whole-model quantization driver: join params ↔ activation stats by path.

This is the tree-level orchestration behind every quantization entry point
(engine requantization, benchmark sweeps, dry-run shape inference).  Per
parameter path it:

1. resolves the effective :class:`~repro.core.policy.QuantPolicy` through the
   policy's fnmatch ``overrides`` (mixed precision),
2. resolves the effective policy's ``method`` through the
   :mod:`repro.quant.registry` (no string dispatch),
3. locates the matching activation-statistic leaf (methods with
   ``requires_stats=False`` synthesize a zero statistic), and
4. asks the quantizer for the :class:`~repro.core.ttq.QuantizedTensor`,
   vmapping over leading run / expert dims.

Two execution strategies share the same per-path resolution:

* :func:`quantize_params` — the eager per-leaf driver (one small dispatch
  chain per leaf; the reference semantics and the fallback);
* :class:`FusedRequantPlan` — the serving hot path: leaves are grouped into
  *families* sharing (d', d, quant settings), each family is ONE jitted
  device program that stacks the member weights (leading run / expert dims
  flattened), computes the AWQ diagonals, subtracts the precomputed
  low-rank residuals, and quantizes the whole stack in a single Pallas
  ``ttq_quantize`` dispatch (or one vmapped jnp quantize when the packed
  kernel does not apply).  A whole-model requantization is a handful of
  async-dispatched programs instead of hundreds of per-leaf ops.

Self-speculative decoding (DESIGN.md §11) instantiates TWO plans over the
same parameter tree — the verify policy and a uniform low-bit
``policy.draft_variant()`` — and runs both against one calibration snapshot:
the families differ only in their (bits, group, rank) key, so requant stays
~1 program/family/tree and the draft+verify pair emits at most 2× the
single-tree program count (:class:`~repro.quant.model.QuantizedModel` owns
the pairing and the per-tree delta-gate snapshots).

``repro.core`` keeps thin delegating shims so historical imports
(``repro.core.quantize_params``) continue to work.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.awq import AWQConfig
from repro.core.lowrank import svd_factors
from repro.core.policy import QuantPolicy

# projections sharing their input with a tapped sibling (one tap per input).
STAT_ALIAS = {
    "wk": "wq", "wv": "wq", "wkv_a": "wq", "wu": "wg",
    "w_in": "w_branch", "w_z": "w_x", "w_B": "w_x", "w_C": "w_x", "w_dt": "w_x",
}


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(getattr(p, "key", p)))
    return ".".join(parts)


def _stats_key(rel_path: tuple) -> str:
    """('u0','mix','wq') → 'u0.mix.wq' with alias resolution on the leaf name."""
    *head, leaf = rel_path
    leaf = STAT_ALIAS.get(leaf, leaf)
    return ".".join([*head, leaf])


def _lookup_stats(stats_run: dict, rel_path: tuple):
    key = _stats_key(rel_path)
    if key in stats_run:
        return stats_run[key]
    # expert weights: stats stored per 'experts.wg'/'experts.wd'
    if rel_path[-1] in ("wg", "wu", "wd") and "experts" in rel_path:
        leaf = "wg" if rel_path[-1] in ("wg", "wu") else "wd"
        key2 = ".".join([*rel_path[:-1], leaf])
        if key2 in stats_run:
            return stats_run[key2]
    return None


def _tree_get(tree, path):
    node = tree
    try:
        for p in path:
            key = p.key if isinstance(p, jax.tree_util.DictKey) else (
                p.idx if isinstance(p, jax.tree_util.SequenceKey) else p)
            node = node[key]
        return node
    except (KeyError, IndexError, TypeError):
        return None


def quantize_params(params, stats, policy: QuantPolicy, *,
                    count: float = 1.0, acfg: Optional[AWQConfig] = None,
                    lowrank_tree=None):
    """Quantize the whole model: replace quantizable 2-D/3-D weights by
    :class:`~repro.core.ttq.QuantizedTensor`, joining activation stats by
    param path.

    ``stats`` is the structure produced by ``models.lm.forward(collect_stats=
    True)``: {'stack': [run-dicts of Σx² leaves, leading run dim], ...}.
    Weights whose stats are missing (untapped), that match ``policy.skip``,
    or whose override-resolved method is disabled stay in full precision.
    """
    countf = jnp.asarray(count, jnp.float32)
    # a caller-supplied acfg replaces the policy's *base* statistics config;
    # per-path overrides (p/alpha/lam/form) still apply on top of it
    base = policy if acfg is None else policy.with_(acfg=acfg)

    def per_leaf(path, leaf):
        ps = _path_str(path)
        if not isinstance(leaf, jnp.ndarray) or leaf.ndim < 2 or leaf.ndim > 4:
            return leaf
        eff = base.resolve(ps)
        if not eff.quantizes(ps.split(".")[-1]) or not eff.quantizes(ps):
            return leaf
        qz = eff.quantizer
        eff_acfg = eff.acfg
        parts = ps.split(".")
        ba = _tree_get(lowrank_tree, path) if lowrank_tree is not None else None

        def quant_one(W, stat, BA=None):
            B = A = None
            if BA is not None:
                B, A = BA["B"], BA["A"]
            elif eff.rank > 0 and min(W.shape) > eff.rank:
                B, A = svd_factors(W, eff.rank)
            return qz.quantize_weight(W, stat, countf, eff, eff_acfg, B, A)

        # locate the stats leaf for this weight (stats-free methods need none)
        stat = None
        if qz.requires_stats:
            if parts[0] not in ("stack", "enc_stack"):
                if isinstance(stats, dict) and ps in stats and leaf.ndim == 2:
                    return quant_one(leaf, stats[ps], None)
                return leaf
            run = (stats or {}).get(parts[0])
            if run is None:
                return leaf
            stat = _lookup_stats(run[int(parts[1])], tuple(parts[2:]))
            if stat is None:
                return leaf
        elif (parts[0] in ("stack", "enc_stack") and leaf.ndim >= 3) \
                or (parts[0] not in ("stack", "enc_stack") and leaf.ndim == 2):
            # stacked weights are ≥3-D (run dim); stacked 1-D params (norm
            # scales, decay vectors) must not be mistaken for 2-D weights
            stat = jnp.zeros(leaf.shape[:-2] + leaf.shape[-1:], jnp.float32)
        else:
            return leaf
        if ba is None:
            fn = lambda W, s: quant_one(W, s, None)
            for _ in range(leaf.ndim - 2):           # vmap over run / expert dims
                fn = jax.vmap(fn)
            return fn(leaf, stat)
        fn = quant_one
        for _ in range(leaf.ndim - 2):
            fn = jax.vmap(fn)
        return fn(leaf, stat, ba)

    return jax.tree_util.tree_map_with_path(per_leaf, params)


# ---------------------------------------------------------------------------
# fused whole-tree requantization (the serving hot path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Member:
    """One quantizable leaf inside a family (host-side bookkeeping only)."""

    path: tuple                    # jax key path into the params tree
    path_str: str
    lead: tuple                    # leading run / expert dims, () for 2-D
    dp: int
    d: int
    eff: QuantPolicy               # override-resolved policy for this path
    stat_get: Optional[Callable]   # stats tree → lead+(d,) array; None → zeros
    has_ba: bool

    @property
    def n(self) -> int:
        out = 1
        for s in self.lead:
            out *= s
        return out


class FusedRequantPlan:
    """Whole-model requantization as one jitted device program per family.

    Built once per (params structure × stats structure × policy).  Families
    group leaves by ``(d', d, quant settings, low-rank presence)``; each
    family's program concatenates the member weights into one (N, d', d)
    stack, computes the per-row AWQ diagonal D from the stacked statistics,
    subtracts the precomputed low-rank residual, and quantizes in ONE
    dispatch — the Pallas ``ttq_quantize`` kernel (batched over N via vmap:
    a single pallas_call with a leading batch grid axis) when the policy's
    packed path + :class:`~repro.core.policy.KernelConfig` apply, else one
    vmapped jnp ``awq_quantize``.  Either way the whole family is a single
    XLA program, async-dispatched, whose results double-buffer under
    :class:`~repro.quant.model.QuantizedModel`.

    Methods with a custom ``quantize_weight`` (anything that is not the
    registry's ``_BaseQuantizer`` closed form) fall back to the eager
    per-leaf path for those leaves — correctness first.

    ``run(params, stats, count, lowrank_tree, only=...)`` returns the full
    quantized parameter tree; ``only`` (a set of family keys) restricts the
    dispatch to a subset — the delta-gate path — with the remaining leaves
    filled from ``reuse`` (previous :class:`QuantizedTensor`s by path).
    """

    def __init__(self, params, stats, policy: QuantPolicy, *,
                 acfg: Optional[AWQConfig] = None, lowrank_tree=None,
                 pctx=None):
        from .registry import _BaseQuantizer
        base = policy if acfg is None else policy.with_(acfg=acfg)
        self.policy = policy
        # shard-local requant: with a mesh, every family program pins its
        # QuantizedTensor outputs to the serving layout (parallel/rules.py)
        # so each weight shard quantizes in place — the only cross-device
        # traffic is the per-column diagonal stats (already replicated)
        self.pctx = pctx if (pctx is not None and pctx.mesh is not None) \
            else None
        self.families: Dict[tuple, List[_Member]] = {}
        self.eager: List[_Member] = []
        self._family_fns: Dict[tuple, Callable] = {}
        self._drift_fn = None

        def visit(path, leaf):
            ps = _path_str(path)
            if not isinstance(leaf, jnp.ndarray) or leaf.ndim < 2 or leaf.ndim > 4:
                return
            eff = base.resolve(ps)
            if not eff.quantizes(ps.split(".")[-1]) or not eff.quantizes(ps):
                return
            qz = eff.quantizer
            parts = ps.split(".")
            dp, d = leaf.shape[-2:]
            lead = tuple(leaf.shape[:-2])
            stat_get: Optional[Callable] = None
            if qz.requires_stats:
                if parts[0] not in ("stack", "enc_stack"):
                    if not (isinstance(stats, dict) and ps in stats
                            and leaf.ndim == 2):
                        return
                    stat_get = (lambda st, _k=ps: st[_k])
                else:
                    run = (stats or {}).get(parts[0])
                    if run is None:
                        return
                    idx = int(parts[1])
                    rel = tuple(parts[2:])
                    if _lookup_stats(run[idx], rel) is None:
                        return
                    # resolve the concrete key once (alias + expert fallback)
                    key = _stats_key(rel)
                    if key not in run[idx]:
                        leafname = "wg" if rel[-1] in ("wg", "wu") else "wd"
                        key = ".".join([*rel[:-1], leafname])
                    stat_get = (lambda st, _r=parts[0], _i=idx, _k=key:
                                st[_r][_i][_k])
            elif not ((parts[0] in ("stack", "enc_stack") and leaf.ndim >= 3)
                      or (parts[0] not in ("stack", "enc_stack")
                          and leaf.ndim == 2)):
                return                      # stacked 1-D params are not weights
            ba = _tree_get(lowrank_tree, path) if lowrank_tree is not None \
                else None
            has_ba = ba is not None
            mem = _Member(path=tuple(path), path_str=ps, lead=lead, dp=dp,
                          d=d, eff=eff, stat_get=stat_get, has_ba=has_ba)
            # eager per-leaf fallback for (a) custom closed forms and (b)
            # leaves the precomputed low-rank tree does not cover but whose
            # policy rank demands an inline SVD (matches quantize_params)
            inline_svd = (not has_ba and eff.rank > 0
                          and min(dp, d) > eff.rank)
            if (type(qz).quantize_weight is not _BaseQuantizer.quantize_weight
                    or inline_svd):
                self.eager.append(mem)
                return
            qcfg = eff.qcfg
            if qcfg.layout != "row":
                qcfg = dataclasses.replace(qcfg, layout="row")
            # eff.rank is part of the key: members with low-rank factors
            # concatenate their (d', r)/(r, d) B/A stacks, so mixed ranks
            # (per-layer rank overrides) must land in separate families;
            # on a mesh so is the TP role, which fixes the family's layout
            tp = None
            if self.pctx is not None:
                from repro.parallel.rules import tp_role
                experts = leaf.ndim - 2 - ("stack" in ps) > 0
                tp = "ep" if experts else tp_role(ps, self.pctx.model_axis)
            key = (dp, d, qcfg, eff.acfg, eff.method, eff.packed, has_ba,
                   eff.rank, tp)
            self.families.setdefault(key, []).append(mem)

        jax.tree_util.tree_map_with_path(lambda p, l: visit(p, l) or None,
                                         params)
        for key in self.families:
            self._family_fns[key] = jax.jit(partial(self._run_family, key))

    @property
    def compiled_programs(self) -> int:
        """Programs resident in the per-family jit caches.  Steady state is
        one per family: a growing count means some family argument is
        changing shape/dtype between requants (a recompile regression —
        DESIGN.md §"Static analysis & runtime invariants")."""
        return sum(fn._cache_size() for fn in self._family_fns.values())

    # ------------------------------------------------------------- execution

    @property
    def n_layers(self) -> int:
        """Total quantized-leaf count (stacked leaves count once per path)."""
        return sum(len(ms) for ms in self.families.values()) + len(self.eager)

    def _gather(self, members, params, stats, count, lowrank_tree):
        countf = jnp.asarray(count, jnp.float32)
        Ws, Ss, Bs, As = [], [], [], []
        for m in members:
            Ws.append(_tree_get(params, m.path))
            if m.stat_get is not None:
                Ss.append(m.stat_get(stats))
            else:
                Ss.append(jnp.zeros(m.lead + (m.d,), jnp.float32))
            if m.has_ba:
                ba = _tree_get(lowrank_tree, m.path)
                Bs.append(ba["B"])
                As.append(ba["A"])
        return Ws, Ss, countf, Bs, As

    def _run_family(self, key, Ws, Ss, countf, Bs, As):
        """ONE device program: stack → D → (W−BA)∘D → quantize → split."""
        from repro.core.ttq import QuantizedTensor, pack_weight, packable
        from .registry import get_quantizer
        dp, d, qcfg, eff_acfg, method, packed_on, has_ba, _rank, tp = key
        members = self.families[key]
        qz = get_quantizer(method)
        # the stack keeps the weights' own dtype: the quantizers cast per
        # tile, so a bf16 family never holds an f32 copy of itself
        W = jnp.concatenate([w.reshape(-1, dp, d) for w in Ws],
                            axis=0)                              # (N, d', d)
        S = jnp.concatenate([s.reshape(-1, d) for s in Ss], axis=0)
        D = jax.vmap(lambda s: qz.diag(s, countf, eff_acfg, d))(S)   # (N, d)
        if has_ba:
            B = jnp.concatenate([b.reshape(-1, dp, b.shape[-1])
                                 for b in Bs], axis=0)
            A = jnp.concatenate([a.reshape(-1, a.shape[-2], d)
                                 for a in As], axis=0)
            W = W.astype(jnp.float32) - jnp.einsum(
                "nor,nrd->nod", B.astype(jnp.float32), A.astype(jnp.float32))
        pack = packed_on and packable(qcfg.bits, d)
        from repro.kernels import ops as kops
        # on a mesh the kernel runs shard-locally over the family's own
        # layout; an expert stack (or a split that breaks groups) takes
        # the jnp quantizer, which GSPMD partitions
        kernel_ok = (pack and self.policy.kernel.use_pallas
                     and qcfg.bits in (2, 4, 8) and not qcfg.symmetric
                     and qcfg.nu == 1.0
                     and kops.tp_quantize_ok(self.pctx, tp, W, bits=qcfg.bits,
                                             group_size=qcfg.group_size))
        if kernel_ok:
            pk, Sc, Z = kops.ttq_quantize_tp(
                W, D, bits=qcfg.bits, group_size=qcfg.group_size,
                pctx=self.pctx, tp=tp, **self.policy.kernel.quant_kw)
            wint = None
        else:
            from repro.core.awq import awq_quantize
            wint, Sc, Z = jax.vmap(
                lambda w, dd: awq_quantize(w, dd, qcfg))(W, D)
            Sc, Z = Sc.swapaxes(1, 2), Z.swapaxes(1, 2)          # K-major
            if pack:
                pk = jax.vmap(lambda w: pack_weight(w, qcfg.bits))(wint)
                wint = None
            else:
                pk = None
                wint = wint.swapaxes(1, 2)
        dinv = (1.0 / D).astype(jnp.float32)
        out, off = [], 0
        for i, m in enumerate(members):
            n = m.n
            sl = slice(off, off + n)
            off += n

            def shaped(x, m=m):
                return None if x is None else x.reshape(m.lead + x.shape[1:])
            qt = QuantizedTensor(
                wint=shaped(None if wint is None else wint[sl]),
                packed=shaped(None if pk is None else pk[sl]),
                scale=shaped(Sc[sl]), zero=shaped(Z[sl]),
                dinv=shaped(dinv[sl]),
                B=Bs[i] if has_ba else None, A=As[i] if has_ba else None,
                bits=qcfg.bits, group_size=qcfg.group_size,
                out_features=dp, in_features=d)
            if self.pctx is not None:
                from repro.parallel.rules import constrain_qt
                qt = constrain_qt(m.path_str, qt, self.pctx)
            out.append(qt)
        return out

    def program_texts(self, params, stats, count, lowrank_tree=None):
        """{family key: compiled text of its requant program} — what a check
        reads to see which Pallas kernels a family runs (``tpu_custom_call``
        ops on a TPU)."""
        return {key: self._family_fns[key].lower(*self._gather(
                    members, params, stats, count, lowrank_tree))
                .compile().as_text()
                for key, members in self.families.items()}

    def _eager_leaf(self, m: _Member, params, stats, count, lowrank_tree):
        """Per-leaf fallback for methods with a custom closed form."""
        countf = jnp.asarray(count, jnp.float32)
        leaf = _tree_get(params, m.path)
        stat = m.stat_get(stats) if m.stat_get is not None \
            else jnp.zeros(m.lead + (m.d,), jnp.float32)
        ba = _tree_get(lowrank_tree, m.path) if m.has_ba else None
        qz = m.eff.quantizer

        def quant_one(W, s, BA=None):
            B = A = None
            if BA is not None:
                B, A = BA["B"], BA["A"]
            elif m.eff.rank > 0 and min(W.shape) > m.eff.rank:
                B, A = svd_factors(W, m.eff.rank)
            return qz.quantize_weight(W, s, countf, m.eff, m.eff.acfg, B, A)

        if ba is None:
            fn = lambda W, s: quant_one(W, s, None)
            for _ in range(len(m.lead)):
                fn = jax.vmap(fn)
            return fn(leaf, stat)
        fn = quant_one
        for _ in range(len(m.lead)):
            fn = jax.vmap(fn)
        return fn(leaf, stat, ba)

    def run(self, params, stats, count, lowrank_tree=None, *, only=None,
            reuse: Optional[Dict[str, Any]] = None):
        """Quantize the tree; families not in ``only`` (when given) are
        filled from ``reuse`` ({path_str: QuantizedTensor}) or left fp."""
        results: Dict[str, Any] = dict(reuse or {})
        for key, members in self.families.items():
            if only is not None and key not in only:
                continue
            args = self._gather(members, params, stats, count, lowrank_tree)
            qts = self._family_fns[key](*args)
            for m, qt in zip(members, qts):
                results[m.path_str] = qt
        for m in self.eager:
            if only is not None and ("eager", m.path_str) not in only:
                continue
            results[m.path_str] = self._eager_leaf(m, params, stats, count,
                                                   lowrank_tree)
        return jax.tree_util.tree_map_with_path(
            lambda p, l: results.get(_path_str(p), l), params)

    # ------------------------------------------------------------ delta gate

    def drift(self, stats, count, last_D: Dict[str, Any]) -> Dict[str, float]:
        """Relative-L2 drift of the activation diagonal D per leaf since the
        snapshot in ``last_D`` ({path_str: (N, d) f32}).  Leaves without a
        snapshot are omitted (the caller must requantize them).  One small
        jitted program + one host transfer of scalars per call."""
        members = [m for ms in self.families.values() for m in ms] + self.eager
        tracked = [m for m in members if m.path_str in last_D]
        if not tracked:
            return {}
        if self._drift_fn is None:
            def fn(stats, countf, prevs):
                outs = []
                for m, prev in zip(tracked, prevs):
                    s = (m.stat_get(stats) if m.stat_get is not None
                         else jnp.zeros(m.lead + (m.d,))).reshape(-1, m.d)
                    qz = m.eff.quantizer
                    Dn = jax.vmap(lambda ss: qz.diag(ss, countf, m.eff.acfg,
                                                     m.d))(s)
                    Dp = prev.reshape(-1, m.d)
                    num = jnp.linalg.norm(Dn - Dp, axis=-1)
                    den = jnp.linalg.norm(Dp, axis=-1) + 1e-12
                    outs.append(jnp.max(num / den))
                return jnp.stack(outs)
            self._drift_fn = jax.jit(fn)
            self._drift_members = [m.path_str for m in tracked]
        if [m.path_str for m in tracked] != self._drift_members:
            self._drift_fn = None           # snapshot set changed → rebuild
            return self.drift(stats, count, last_D)
        vals = self._drift_fn(stats, jnp.asarray(count, jnp.float32),
                              [last_D[m.path_str] for m in tracked])
        import numpy as np
        return {m.path_str: float(v) for m, v in zip(tracked,
                                                     np.asarray(vals))}

    def gate(self, drifts: Dict[str, float], threshold: float,
             have: set) -> tuple:
        """Family keys to requantize: any member whose drift ≥ threshold, or
        without a previous QuantizedTensor (``have`` = reusable paths)."""
        only = set()
        n_requant = n_skip = 0
        for key, members in self.families.items():
            hit = [m for m in members
                   if m.path_str not in have
                   or drifts.get(m.path_str, float("inf")) >= threshold]
            if hit:
                only.add(key)
                n_requant += len(members)
            else:
                n_skip += len(members)
        for m in self.eager:
            if (m.path_str not in have
                    or drifts.get(m.path_str, float("inf")) >= threshold):
                only.add(("eager", m.path_str))
                n_requant += 1
            else:
                n_skip += 1
        return only, n_requant, n_skip


def lowrank_tree(params, policy: QuantPolicy):
    """Offline, data-free SVD factors for every quantizable 2/3-D weight.

    Returns a pytree of {'B','A'} dicts (None where ineligible) matching the
    param container structure, vmapped over leading run / expert dims, or
    None when no path resolves to rank > 0 (base policy *or* overrides).
    Computed once per model; :func:`quantize_params` consumes it via
    ``lowrank_tree=`` so requantization never re-runs the SVD.
    """
    found = False

    def per_leaf(path, leaf):
        nonlocal found
        ps = _path_str(path)
        eff = policy.resolve(ps)
        last = ps.split(".")[-1]
        if (getattr(leaf, "ndim", 0) in (2, 3) and eff.rank > 0
                and eff.quantizes(last) and eff.quantizes(ps)
                and min(leaf.shape[-2:]) > eff.rank):
            found = True
            fn = lambda W: dict(zip(("B", "A"), svd_factors(W, eff.rank)))
            for _ in range(leaf.ndim - 2):
                fn = jax.vmap(fn)
            return fn(leaf)
        return None

    tree = jax.tree_util.tree_map_with_path(per_leaf, params)
    return tree if found else None
