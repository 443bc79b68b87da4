"""Per-layer quantization policy — which matmuls get TTQ'd and how.

A ``QuantPolicy`` is attached to a model config; the serving engine and the
benchmarks consult it to decide, per named projection, the bits / groupsize /
rank / activation-statistic settings, and whether the packed-int Pallas kernel
or the fake-quant (QDQ) path is used.

Mixed precision is expressed declaratively via ``overrides``: an ordered
tuple of ``(fnmatch pattern, partial-policy delta)`` pairs resolved against
the full parameter path (e.g. ``stack.0.u0.mix.wq``).  Every matching entry
is applied in order (later entries win on conflicting fields), so a policy
like::

    ttq_policy(bits=3, group_size=64).with_overrides(
        override("*.mix.*", bits=4, group_size=32),   # attention: finer
        override("stack.*.u0.*", bits=8),             # first block: 8-bit
    )

gives attention projections 4-bit g=32, the first block 8-bit, and everything
else the 3-bit g=64 base.  Deltas may set top-level fields (``method``,
``rank``, ``packed``), QDQ fields (``bits``, ``group_size``, ``symmetric``,
``nu``, ``layout``) and statistic fields (``p``, ``alpha``, ``lam``,
``form``).  Resolution happens once per parameter path in
:func:`repro.quant.api.quantize_params` (see DESIGN.md).

The method name is resolved through :mod:`repro.quant.registry` — adding a
method is a registry entry, not another ``if`` chain.
"""
from __future__ import annotations

import dataclasses
import fnmatch
from typing import Optional

from .awq import AWQConfig
from .kvquant import KVCacheConfig
from .qdq import QuantConfig

_QCFG_FIELDS = {f.name for f in dataclasses.fields(QuantConfig)}
_ACFG_FIELDS = {f.name for f in dataclasses.fields(AWQConfig)}


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Static weight-kernel dispatch config (hashable → usable as a jit
    static arg, threaded like :class:`~repro.core.kvquant.KVCacheConfig`).

    ``use_pallas=True`` routes every decode matmul over a *packed*
    :class:`~repro.core.ttq.QuantizedTensor` through the fused Pallas
    ``ttq_gemm`` (in-kernel unpack + dequant) instead of the
    jnp dequantize-then-einsum fallback.  Weights without a packed payload
    (``policy.packed=False``, unpackable bit-widths) always take the
    fallback, so the flag is a pure opt-in.

    Block sizes map onto the kernel grids: ``bm/bn/bk`` tile the GEMM
    (T/d'/d axes), ``qbm/qbk`` tile the online-quantize kernel (d'/d axes).
    Defaults are the kernels' MXU-aligned defaults.
    """

    use_pallas: bool = False
    bm: int = 128
    bn: int = 128
    bk: int = 256
    qbm: int = 256
    qbk: int = 512

    @property
    def gemm_kw(self) -> dict:
        return {"bm": self.bm, "bn": self.bn, "bk": self.bk}

    @property
    def quant_kw(self) -> dict:
        return {"bm": self.qbm, "bk": self.qbk}


FUSED_KERNELS = KernelConfig(use_pallas=True)


def override(pattern: str, **delta) -> tuple:
    """Normalize one override to a hashable (pattern, ((key, value), ...))."""
    known = _QCFG_FIELDS | _ACFG_FIELDS | {
        "method", "rank", "packed", "per_expert_stats"}
    unknown = set(delta) - known
    if unknown:
        raise ValueError(f"unknown override field(s) {sorted(unknown)}; "
                         f"known: {sorted(known)}")
    return (pattern, tuple(sorted(delta.items())))


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    method: str = "ttq"            # any name in repro.quant.registry
    qcfg: QuantConfig = QuantConfig(bits=4, group_size=32, layout="row")
    acfg: AWQConfig = AWQConfig()
    rank: int = 0                  # low-rank residual rank r (0 = off)
    skip: tuple = ("embed*", "lm_head", "*norm*", "router*",  # fnmatch patterns
                   "w_gate*", "conv*", "pos_embed",           # tiny/elementwise
                   "gamma", "beta")                           # norm params
    packed: bool = False           # real int path (Pallas kernel) vs fake-quant
    per_expert_stats: bool = True  # MoE: accumulate D per expert
    overrides: tuple = ()          # ((pattern, ((field, value), ...)), ...)
    # KV-cache memory layout (global, not per-path: the cache is allocated
    # once per engine — see DESIGN.md §"KV-cache layout").  Orthogonal to the
    # weight method: NO_QUANT weights + int8 cache is a valid combination.
    kvcache: KVCacheConfig = KVCacheConfig()
    # weight-kernel dispatch (global, like kvcache: one decode program per
    # engine) — Pallas ttq_gemm on packed weights vs the jnp fallback, plus
    # the fused single-dispatch requantization kernel (DESIGN.md §7).
    kernel: KernelConfig = KernelConfig()

    @property
    def quantizer(self):
        """The registered method object for ``self.method``."""
        from repro.quant.registry import get_quantizer
        return get_quantizer(self.method)

    @property
    def enabled(self) -> bool:
        return self.quantizer.enabled

    def methods(self) -> tuple:
        """All method names this policy can resolve to (base + overrides)."""
        names = [self.method]
        for _, delta in self.overrides:
            for k, v in delta:
                if k == "method" and v not in names:
                    names.append(v)
        return tuple(names)

    @property
    def any_enabled(self) -> bool:
        """True if the base method or any override-reachable method is on."""
        from repro.quant.registry import get_quantizer
        return any(get_quantizer(m).enabled for m in self.methods())

    def quantizes(self, name: str) -> bool:
        if not self.enabled:
            return False
        return not any(fnmatch.fnmatch(name, pat) for pat in self.skip)

    def with_(self, **kw) -> "QuantPolicy":
        return dataclasses.replace(self, **kw)

    # ----------------------------------------------------- per-layer overrides

    def with_overrides(self, *ovr) -> "QuantPolicy":
        """Append overrides (``override(...)`` tuples or (pattern, dict))."""
        norm = tuple(
            o if isinstance(o[1], tuple) else override(o[0], **o[1])
            for o in ovr)
        return dataclasses.replace(self, overrides=self.overrides + norm)

    def _apply(self, delta: tuple) -> "QuantPolicy":
        top, qkw, akw = {}, {}, {}
        for k, v in delta:
            if k in _QCFG_FIELDS:
                qkw[k] = v
            elif k in _ACFG_FIELDS:
                akw[k] = v
            else:
                top[k] = v
        if qkw:
            top["qcfg"] = dataclasses.replace(self.qcfg, **qkw)
        if akw:
            top["acfg"] = dataclasses.replace(self.acfg, **akw)
        return dataclasses.replace(self, **top)

    def resolve(self, path: str) -> "QuantPolicy":
        """Effective policy for one parameter path (all matches, in order)."""
        eff = self
        for pat, delta in self.overrides:
            if fnmatch.fnmatch(path, pat):
                eff = eff._apply(delta)
        return eff

    def draft_variant(self, bits: int = 4, group_size: int = 0) -> "QuantPolicy":
        """Uniform low-bit sibling for self-speculative drafting
        (DESIGN.md §11): same method / skip set / KV-cache layout / kernel
        dispatch, but one flat ``bits`` everywhere (``group_size`` 0 keeps
        the base group), rank 0 and no per-layer overrides — the draft tree
        quantizes as ONE family-light pass and its decode matmuls skip the
        low-rank correction, which is what makes drafting cheap."""
        if not self.enabled:
            return self
        gs = group_size or self.qcfg.group_size
        return dataclasses.replace(
            self, qcfg=dataclasses.replace(self.qcfg, bits=bits,
                                           group_size=gs),
            rank=0, overrides=())


NO_QUANT = QuantPolicy(method="none")


def ttq_policy(bits: int = 4, group_size: int = 32, rank: int = 16,
               packed: bool = False, kv_dtype: str = "bf16",
               kv_group_size: int = 0, **kw) -> QuantPolicy:
    kw.setdefault("kvcache", KVCacheConfig(dtype=kv_dtype,
                                           group_size=kv_group_size))
    return QuantPolicy(
        method="ttq",
        qcfg=QuantConfig(bits=bits, group_size=group_size, layout="row"),
        rank=rank, packed=packed, **kw,
    )
