"""QuantizedModel — the calibrate → requantize → decode_params facade.

Owns everything the TTQ lifecycle needs around a parameter tree:

* a :class:`~repro.quant.session.CalibrationSession` accumulating the live
  workload's activation statistics (decay, fork/merge for multi-stream),
* the data-free low-rank factor tree (computed **once**; requantization
  reuses it — no per-requant SVD),
* the current quantized parameter tree and a requantization counter,
* the :class:`~repro.quant.api.FusedRequantPlan` — requantization runs as
  one jitted device program per weight family (built lazily on the first
  requantize, reused afterwards) instead of an eager per-leaf ``tree_map``,
* the **delta gate**: ``requantize(threshold=…)`` re-quantizes only layers
  whose activation diagonal D drifted (relative L2) beyond the threshold
  since their last snapshot, reusing the previous
  :class:`~repro.core.ttq.QuantizedTensor` elsewhere.

Typical serving loop::

    qm = QuantizedModel(params, policy, halflife=ecfg.stats_halflife)
    ...
    qm.calibrate(prefill_stats, tokens=n_prefill_tokens)
    qm.requantize()                      # async: a handful of device programs
    logits = decode(qm.decode_params, ...)

Requantization never blocks the host: the family programs are
async-dispatched and the returned tree holds device futures — subsequent
decode work is *enqueued* behind them, not waited on.  With
``double_buffer=True`` the swap is additionally gated on device readiness:
``decode_params`` keeps returning the previous tree until every leaf of the
new one reports ``is_ready()``, so queued decode blocks keep hitting the old
weights while the requant runs.  That makes emitted tokens depend on device
timing (how many chunks land before the swap), so it is an explicit opt-in —
the default swaps deterministically at the requantize call.

Multi-stream: ``child = qm.fork()`` shares params and low-rank factors but
gets an independent calibration session; join with
``qm.adopt(child.session)`` (exact — the statistics are additive).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.core.awq import AWQConfig
from repro.core.policy import QuantPolicy

from .api import FusedRequantPlan, lowrank_tree, quantize_params
from .guards import GuardConfig, qt_health
from .session import CalibrationSession


_AUTO = object()   # sentinel: compute the low-rank tree from the policy


class QuantizedModel:
    def __init__(self, params: Any, policy: QuantPolicy, *,
                 acfg: Optional[AWQConfig] = None, halflife: float = 0.0,
                 session: Optional[CalibrationSession] = None,
                 lowrank: Any = _AUTO, fused: bool = True,
                 double_buffer: bool = False, pctx=None,
                 draft_policy: Optional[QuantPolicy] = None,
                 health_gate: Optional[GuardConfig] = None):
        self.params = params
        self.policy = policy
        self.acfg = acfg
        self.fused = fused
        self.double_buffer = double_buffer
        self.pctx = pctx                 # mesh → shard-local requant plans
        self.session = session if session is not None else \
            CalibrationSession(halflife=halflife)
        if lowrank is _AUTO:
            self.lowrank_tree = lowrank_tree(params, policy) \
                if policy.any_enabled else None
        else:
            self.lowrank_tree = lowrank
        self.qparams = None
        self.n_requants = 0
        # fused-plan state (lazy: the plan needs a concrete stats structure)
        self._plan: Optional[FusedRequantPlan] = None
        self._plan_key = None
        self._qt_by_path: dict = {}      # path_str → last QuantizedTensor
        self._last_D: dict = {}          # path_str → (lead..., d) f32 snapshot
        self._pending = None             # double buffer: not-yet-ready tree
        # requant health gate (DESIGN.md §12): with a GuardConfig, every
        # candidate tree is validated (finite scales/zero/D⁻¹, bounded D⁻¹
        # drift) BEFORE it can reach a swap or refresh the delta-gate
        # snapshots; rejections keep serving the last-good tree
        self.health_gate = health_gate
        self.requant_rejections = 0
        self.last_health_drift = 0.0
        self._fault_hook = None          # designated injection site: called
                                         # with the candidate tree pre-
                                         # validation (serving/faults.py)
        # self-speculative draft tree (DESIGN.md §11): a second quantized
        # tree from the SAME calibration snapshot.  None → no draft tree;
        # a disabled draft policy (e.g. NO_QUANT) keeps draft_params on the
        # fp weights while the verify tree quantizes normally.
        self.draft_policy = draft_policy
        self._draft_enabled = (draft_policy is not None
                               and draft_policy.any_enabled)
        if self._draft_enabled and not fused:
            raise ValueError("draft_policy (self-speculative decoding) needs "
                             "the fused requant plan; construct "
                             "QuantizedModel(fused=True) (the default)")
        self.draft_lowrank_tree = lowrank_tree(params, draft_policy) \
            if self._draft_enabled and draft_policy.rank > 0 else None
        self.draft_qparams = None
        self._draft_plan: Optional[FusedRequantPlan] = None
        self._draft_qt_by_path: dict = {}
        self._draft_last_D: dict = {}
        self._draft_pending = None
        # delta-gate accounting (read by the engine / serve summary;
        # verify-tree counts — the draft tree gates with its own snapshots)
        self.last_requant_layers = 0
        self.last_skipped_layers = 0
        self.total_requant_layers = 0
        self.total_skipped_layers = 0

    # -------------------------------------------------------------- lifecycle

    def calibrate(self, stats: Any, tokens: float,
                  provenance: tuple = ()) -> "QuantizedModel":
        """Fold one prefill's activation statistics into the session.
        ``provenance`` (request ids) rides into the quarantine log when a
        guarded session rejects the update."""
        self.session.update(stats, tokens, provenance=provenance)
        return self

    def _active(self) -> bool:
        from .registry import get_quantizer
        pols = [self.policy] + \
            ([self.draft_policy] if self._draft_enabled else [])
        active = [q for pol in pols for q in map(get_quantizer,
                                                 pol.methods()) if q.enabled]
        if not active:
            return False
        if not self.session.calibrated and all(q.requires_stats
                                               for q in active):
            return False
        return True

    @property
    def compiled_programs(self) -> int:
        """Jit-cache entries of the fused requant plan(s) (0 before the first
        requant builds them; draft + verify trees sum — the ≤2× budget of
        DESIGN.md §11)."""
        n = self._plan.compiled_programs if self._plan is not None else 0
        if self._draft_plan is not None:
            n += self._draft_plan.compiled_programs
        return n

    def _ensure_plan(self, stats) -> Optional[FusedRequantPlan]:
        """Build the fused plan(s) for the current tree structures.

        Returns the *verify* plan, or None when the verify policy is fully
        disabled (draft-only mode: a quantized draft speculates for the fp
        model — DESIGN.md §11); the draft plan is built either way.
        """
        key = (jax.tree_util.tree_structure(self.params),
               jax.tree_util.tree_structure(stats))
        if self._plan_key != key:
            self._plan = FusedRequantPlan(self.params, stats, self.policy,
                                          acfg=self.acfg,
                                          lowrank_tree=self.lowrank_tree,
                                          pctx=self.pctx) \
                if self.policy.any_enabled else None
            if self._draft_enabled:
                self._draft_plan = FusedRequantPlan(
                    self.params, stats, self.draft_policy, acfg=self.acfg,
                    lowrank_tree=self.draft_lowrank_tree, pctx=self.pctx)
            self._plan_key = key
        return self._plan

    def requant_program_texts(self) -> Dict[tuple, str]:
        """Compiled text of each family program of the verify plan on the
        session's current statistics ({} before the plan exists)."""
        if self._plan is None:
            return {}
        stats, count = self.session.as_calib()
        return self._plan.program_texts(self.params, stats, count,
                                        self.lowrank_tree)

    def requantize(self, threshold: Optional[float] = None):
        """(Re)quantize from the session's current statistics.

        ``threshold`` arms the delta gate: only leaves whose activation
        diagonal D drifted by at least ``threshold`` in relative L2 since
        their last quantization are re-quantized (0 → everything, ∞ →
        nothing); leaves below the gate reuse their previous
        ``QuantizedTensor``.  ``None`` (default) requantizes everything
        without computing drift.

        Returns the quantized tree, or None when every reachable method
        (base policy or override) is disabled, or when all enabled methods
        still need statistics the session doesn't have yet.
        """
        if not self._active():
            return None
        stats, count = self.session.as_calib()
        if not self.fused:
            if threshold is not None:
                raise ValueError(
                    "requantize(threshold=...) — the delta gate — needs the "
                    "fused plan; construct QuantizedModel(fused=True) "
                    "(the default) or drop the threshold")
            self.qparams = quantize_params(
                self.params, stats, self.policy, count=count,
                acfg=self.acfg, lowrank_tree=self.lowrank_tree)
            self.n_requants += 1
            return self.qparams
        plan = self._ensure_plan(stats)
        tree = None
        if plan is not None:
            tree, n_requant, n_skip = self._attempt(
                plan, self.lowrank_tree, self._qt_by_path, self._last_D,
                stats, count, threshold)
            if tree is None:
                # sustained corruption (the immediate clean retry failed
                # too): the newest accepted calibration update is the prime
                # suspect — drop it and keep serving the last-good tree.
                # n_requants stays put, so the engine's cadence re-arms.
                self.session.rollback(1)
                return None
            self.last_requant_layers = n_requant
            self.last_skipped_layers = n_skip
            self.total_requant_layers += n_requant
            self.total_skipped_layers += n_skip
            if self.double_buffer and self.qparams is not None:
                self._pending = tree     # swap when device-ready (opt-in:
            else:                        # token timing becomes device-bound)
                self.qparams = tree
        if self._draft_plan is not None:
            # draft tree: same stats snapshot, same delta-gate semantics,
            # its own D snapshots (the gates may fire on different steps)
            dtree, _, _ = self._attempt(
                self._draft_plan, self.draft_lowrank_tree,
                self._draft_qt_by_path, self._draft_last_D,
                stats, count, threshold)
            if dtree is None and plan is None:
                # draft-only mode: the draft IS the primary tree
                self.session.rollback(1)
                return None
            if dtree is not None:
                if self.double_buffer and self.draft_qparams is not None:
                    self._draft_pending = dtree
                else:
                    self.draft_qparams = dtree
                if tree is None:
                    tree = dtree         # draft-only mode: report the draft
            # a rejected draft beside a healthy verify tree keeps its old
            # draft (speculation stays token-correct — the verify tree
            # decides every emitted token; only acceptance rate suffers)
        self.n_requants += 1             # tree so cadence accounting (the
        return tree                      # engine's note_requant) still fires

    def _attempt(self, plan, lowrank, qt_by_path, last_D, stats, count,
                 threshold):
        """One tree's requant with the health gate: a rejected candidate is
        retried once immediately (transient corruption — a flipped device
        buffer, an injected fault — yields a clean tree on the very next
        dispatch from the same stats), then given up on."""
        tries = 2 if self.health_gate is not None else 1
        for _ in range(tries):
            tree, n_requant, n_skip = self._run_plan(
                plan, lowrank, qt_by_path, last_D, stats, count, threshold)
            if tree is not None:
                return tree, n_requant, n_skip
        return None, 0, 0

    def _run_plan(self, plan, lowrank, qt_by_path, last_D, stats, count,
                  threshold):
        """Run one tree's fused plan (gate → family programs → health gate →
        snapshot refresh).  Returns (tree, n_requant, n_skip); a
        health-rejected candidate returns (None, 0, 0) *without* touching
        the delta-gate snapshots — nothing of it survives."""
        only = None
        n_requant, n_skip = plan.n_layers, 0
        if threshold is not None and qt_by_path:
            drifts = plan.drift(stats, count, last_D)
            only, n_requant, n_skip = plan.gate(drifts, threshold,
                                                set(qt_by_path))
        tree = plan.run(self.params, stats, count, lowrank,
                        only=only, reuse=qt_by_path)
        if self._fault_hook is not None:
            tree = self._fault_hook(tree)
        if self.health_gate is not None:
            prev = {p: qt.dinv for p, qt in qt_by_path.items()
                    if qt.dinv is not None}
            ok, drift = qt_health(tree, prev,
                                  self.health_gate.requant_max_drift)
            self.last_health_drift = drift
            if not ok:
                self.requant_rejections += 1
                return None, 0, 0
        # refresh the per-path snapshot for everything that was requantized
        from repro.core.ttq import QuantizedTensor

        def note(path, leaf):
            if isinstance(leaf, QuantizedTensor):
                from .api import _path_str
                ps = _path_str(path)
                if qt_by_path.get(ps) is not leaf:
                    last_D[ps] = 1.0 / leaf.dinv
                qt_by_path[ps] = leaf

        jax.tree_util.tree_map_with_path(
            lambda p, l: note(p, l),
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        return tree, n_requant, n_skip

    def _swap_if_ready(self):
        if self._pending is not None:
            leaves = jax.tree.leaves(self._pending)
            if all(l.is_ready() for l in leaves if hasattr(l, "is_ready")):
                self.qparams, self._pending = self._pending, None
        if self._draft_pending is not None:
            leaves = jax.tree.leaves(self._draft_pending)
            if all(l.is_ready() for l in leaves if hasattr(l, "is_ready")):
                self.draft_qparams, self._draft_pending = \
                    self._draft_pending, None

    @property
    def decode_params(self):
        """Latest *device-ready* quantized tree; falls back to the previous
        tree while a requantization is in flight, and to the fp parameters
        before the first requantization."""
        self._swap_if_ready()
        return self.qparams if self.qparams is not None else self.params

    @property
    def draft_params(self):
        """Latest device-ready DRAFT tree (DESIGN.md §11); the fp parameters
        before the first requantization or when the draft policy is disabled
        (a fp draft is a valid — maximally accurate — speculator)."""
        self._swap_if_ready()
        return self.draft_qparams if self.draft_qparams is not None \
            else self.params

    # ------------------------------------------------------------ fork / join

    def fork(self) -> "QuantizedModel":
        """Independent calibration stream sharing params + low-rank factors."""
        return QuantizedModel(self.params, self.policy, acfg=self.acfg,
                              session=self.session.fork(),
                              lowrank=self.lowrank_tree, fused=self.fused,
                              double_buffer=self.double_buffer,
                              pctx=self.pctx, draft_policy=self.draft_policy,
                              health_gate=self.health_gate)

    def adopt(self, session: CalibrationSession) -> "QuantizedModel":
        """Join a forked stream's statistics into this model's session."""
        self.session = self.session.merge(session)
        return self
