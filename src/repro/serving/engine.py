"""TTQEngine — continuous-batching serving with online test-time quantization.

The paper's lifecycle (Fig. 1b) as a slot-based engine:

  submit → [queue] → admit: PREFILL in full precision with the stats tap on
                            (Σ_t x² per linear input feature, additive)
                     → aggregate stats across active prompts
                     → (re)QUANTIZE: D = f(stats); W_int,S,Z = G[(W−BA)∘D]
                       — one fused device program per weight family
                       (FusedRequantPlan), double-buffered so decode keeps
                       serving the previous tree until the swap, and
                       delta-gated (``requant_threshold``): only layers
                       whose D drifted re-quantize
                     → DECODE with the quantized weights in fused K-step
                       blocks; with ``policy.kernel.use_pallas`` (or
                       ``EngineConfig.use_kernels``) every packed-weight
                       matmul dispatches the Pallas ttq_gemm (in-kernel
                       unpack + dequant)

The engine is a thin facade over three parts (DESIGN.md §"Serving
architecture"):

* :class:`~repro.serving.scheduler.Scheduler` — host policy: FIFO queue,
  slot admission (bucketed groups → one batched prefill dispatch each),
  requantization cadence (per-admission or token-budget);
* :class:`~repro.serving.runner.DeviceRunner` — jitted device execution:
  batched prefill and ``lm.decode_many`` (a ``lax.scan`` over
  ``decode_chunk`` decode steps with on-device sampling / EOS / budget /
  capacity masking — one host transfer per K tokens per batch, not one per
  token per slot);
* :class:`repro.quant.QuantizedModel` — TTQ state: stats session (decay),
  low-rank factors computed once, the quantized tree.

Per-prompt calibration (the paper's setting) is the ``max_slots=1`` case;
with batched serving the engine self-calibrates on the aggregate of the
*current* prompts — the statistics are additive sufficient statistics, so
this is the natural generalization (DESIGN.md §"CalibrationSession").

Per-slot positions everywhere → true continuous batching: a new request can
be admitted while other slots are mid-generation (at decode-chunk
boundaries).  The slot caches' memory layout is policy-driven
(``policy.kvcache`` / ``EngineConfig.kv_dtype``): bf16, or int8 /
packed-int4 codes + per-(head, token) f32 scales (DESIGN.md §"KV-cache
layout").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

from repro.core import QuantPolicy
from repro.models.config import ModelConfig
from repro.quant import CalibrationSession, GuardConfig, QuantizedModel
from repro.quant import guards as _guards

from .runner import DeviceRunner
from .scheduler import GenResult, Request, Scheduler, pick_decode_chunk


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4
    max_len: int = 256
    decode_chunk: int = 1           # K: fused decode steps per host sync;
                                    # 0 → auto via pick_decode_chunk(slots)
                                    # (serve.py defaults to auto; the config
                                    # default stays 1 = per-token, the seed
                                    # semantics)
    recalibrate_every: int = 1      # re-quantize after every N admissions
    recalibrate_tokens: int = 0     # >0: token-budget cadence instead
    stats_halflife: int = 0         # >0: exponential decay of stats (updates)
    temperature: float = 0.0
    eos_token: int = -1             # -1 → run to max_new
    prompt_buckets: tuple = (16, 32, 64, 128, 256)
    kv_dtype: str = ""              # "" → policy.kvcache; else bf16|int8|int4
    use_kernels: Optional[bool] = None  # None → policy.kernel.use_pallas.
                                    # Flips ONLY the decode GEMM dispatch
                                    # (bitwise-identical math either way);
                                    # the Pallas ttq_quantize kernel is a
                                    # *policy* choice (policy.kernel) because
                                    # it changes the quantization function
                                    # itself (±1 code ties vs jnp)
    requant_threshold: float = -1.0  # ≥0 → delta-gated requantization
    double_buffer: bool = False     # readiness-gated requant swap (decode
                                    # keeps the old tree until the new one
                                    # is device-ready; tokens become
                                    # device-timing-dependent — opt-in)
    # ---- paged KV pool (DESIGN.md §8) ----
    kv_paged: Optional[bool] = None  # None → policy.kvcache.paged
    kv_block_size: int = 0          # tokens per pool block; 0 → policy
    kv_pool_blocks: int = 0         # physical blocks per layer incl. the
                                    # sink; 0 → capacity-equivalent auto
                                    # (max_slots·max_len/block_size + 1 —
                                    # no preemption ever needed); smaller
                                    # budgets oversubscribe and preempt
    prefix_cache: bool = True       # share quantized prompt-prefix blocks
    # ---- self-speculative decoding (DESIGN.md §11) ----
    speculate_k: int = 0            # W: drafted tokens per verify window
                                    # (0 = off).  Greedy only — auto-off
                                    # when temperature > 0 (rejection-
                                    # sampling acceptance is future work).
                                    # decode_chunk then counts WINDOWS per
                                    # dispatch (auto shrinks it so tokens/
                                    # dispatch stays comparable).
    # ---- robustness layer (DESIGN.md §12) ----
    guards: bool = True             # calibration validation, requant health
                                    # gate, decode fault isolation and the
                                    # degradation ladder.  Off = the exact
                                    # pre-guard engine (decode program
                                    # included — detection costs one
                                    # isfinite reduction per step)
    guard_cfg: GuardConfig = GuardConfig()  # knobs (frozen, shareable)
    deadline_s: float = 0.0         # default per-request wall budget from
                                    # submit (0 = none; submit() overrides
                                    # per request)
    # ---- streaming & SLO scheduling (DESIGN.md §13) ----
    prefill_chunk: int = 0          # >0: ingest prompt tails longer than
                                    # this in fixed-size chunks interleaved
                                    # with decode rounds (plain-attn
                                    # families; paged pools need it to
                                    # divide by block_size).  Also lifts
                                    # the bucket cap on prompt length.
    prefill_budget: int = 0         # padded prefill tokens dispatched per
                                    # engine round (0 → one chunk/round);
                                    # bounds how much a long ingestion can
                                    # stretch running streams' ITL
    max_queue: int = 0              # >0: submit() raises QueueFull at this
                                    # queue depth (the async front end
                                    # awaits instead); 0 = unbounded


class TTQEngine:
    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 ecfg: EngineConfig = EngineConfig(), pctx=None, key=None,
                 draft_policy: Optional[QuantPolicy] = None, faults=None):
        if ecfg.speculate_k > 0 and ecfg.temperature > 0.0:
            # greedy acceptance would bias sampled streams — auto-off until
            # rejection-sampling acceptance lands (DESIGN.md §11)
            ecfg = dataclasses.replace(ecfg, speculate_k=0)
        if ecfg.speculate_k > 0:
            from repro.models.stack import stack_spec
            kinds = {k for ks, _ in stack_spec(cfg) for k in ks}
            if kinds != {"attn"}:
                raise ValueError(
                    f"speculate_k needs a plain-attention family, got "
                    f"{sorted(kinds)} (windowed/latent/recurrent decode "
                    f"states cannot roll back rejected drafts — "
                    f"DESIGN.md §11)")
        if ecfg.prefill_chunk > 0:
            from repro.models.stack import stack_spec
            kinds = {k for ks, _ in stack_spec(cfg) for k in ks}
            if kinds != {"attn"}:
                raise ValueError(
                    f"prefill_chunk needs a plain-attention family, got "
                    f"{sorted(kinds)} (chunked ingestion gathers and "
                    f"extends one per-layer k/v context per chunk — "
                    f"DESIGN.md §13)")
        if ecfg.decode_chunk <= 0:
            ecfg = dataclasses.replace(
                ecfg, decode_chunk=pick_decode_chunk(ecfg.max_slots,
                                                     ecfg.speculate_k))
        self.cfg, self.params, self.policy, self.ecfg = cfg, params, policy, ecfg
        # self-speculative draft tree: the default draft is the policy's
        # uniform low-bit variant; with a NO_QUANT verify policy pass an
        # enabled draft_policy for draft-only quantization (the quantized
        # model speculates for its fp self — see EXPERIMENTS.md)
        self.draft_policy = None
        if ecfg.speculate_k > 0:
            self.draft_policy = (draft_policy if draft_policy is not None
                                 else policy.draft_variant())
        self.pctx = pctx
        # KV-cache memory layout: policy-driven, EngineConfig.kv_dtype wins
        # when set.  Static across the engine's lifetime — every slot cache,
        # the prefill write and the decode read share one layout.
        self.kvcfg = policy.kvcache
        if ecfg.kv_dtype:
            self.kvcfg = dataclasses.replace(self.kvcfg, dtype=ecfg.kv_dtype)
        if ecfg.kv_paged is not None:
            self.kvcfg = dataclasses.replace(self.kvcfg, paged=ecfg.kv_paged)
        if ecfg.kv_block_size:
            self.kvcfg = dataclasses.replace(self.kvcfg,
                                             block_size=ecfg.kv_block_size)
        # paged pool geometry: blocks per layer, block 0 reserved as sink.
        # The auto budget is capacity-equivalent to the dense slab (every
        # slot can hold max_len), so the default never preempts; shrink
        # kv_pool_blocks to oversubscribe (DESIGN.md §8).
        self.num_blocks = 0
        if self.kvcfg.paged:
            if ecfg.max_len % self.kvcfg.block_size:
                raise ValueError(
                    f"max_len={ecfg.max_len} must divide by "
                    f"kv block_size={self.kvcfg.block_size}")
            per_slot = ecfg.max_len // self.kvcfg.block_size
            self.num_blocks = (ecfg.kv_pool_blocks
                               or ecfg.max_slots * per_slot + 1)
            if (ecfg.prefill_chunk > 0
                    and ecfg.prefill_chunk % self.kvcfg.block_size):
                raise ValueError(
                    f"prefill_chunk={ecfg.prefill_chunk} must divide by kv "
                    f"block_size={self.kvcfg.block_size}: chunk boundaries "
                    f"must align with pool blocks so the prefix gather "
                    f"reads whole written blocks")
        # weight-kernel dispatch: policy-driven, EngineConfig.use_kernels
        # wins when set.  Static too — it is baked into the jitted decode.
        # The override is decode-only by design: the GEMM paths feed the
        # same operands to an f32-accumulated dot (bitwise identical in
        # interpret mode; on the TPU equal up to accumulation order), so
        # flipping it does not change tokens, while the fused
        # requant's Pallas ttq_quantize (a different rounding fusion — ±1
        # code ties) stays governed by the policy the QuantizedModel holds.
        self.kncfg = policy.kernel
        if ecfg.use_kernels is not None:
            self.kncfg = dataclasses.replace(self.kncfg,
                                             use_pallas=ecfg.use_kernels)
        # runner first: with a mesh, the fp parameter tree is committed to
        # its sharded layout through the runner (the one component allowed
        # to allocate device memory — TC402/TC405) BEFORE the quant model
        # captures it, so every requant reads already-local weight shards
        self.runner = DeviceRunner(cfg, ecfg, self.kvcfg, kncfg=self.kncfg,
                                   pctx=pctx, key=key,
                                   num_blocks=self.num_blocks)
        self.params = params = self.runner.place_params(params)
        # robustness layer (DESIGN.md §12): one GuardConfig drives the
        # session's update validation, the model's requant health gate, the
        # scheduler's retry budget and the degradation ladder below.  The
        # session/model guards are strictly opt-in at their constructors,
        # so direct QuantizedModel users are untouched.
        guard = ecfg.guard_cfg if ecfg.guards else None
        self.qmodel = QuantizedModel(
            params, policy,
            session=CalibrationSession(halflife=ecfg.stats_halflife,
                                       guard=guard),
            double_buffer=ecfg.double_buffer, pctx=pctx,
            draft_policy=self.draft_policy, health_gate=guard)
        self.scheduler = Scheduler(
            ecfg, exact_buckets=cfg.family in ("hybrid", "ssm"),
            kvcfg=self.kvcfg, num_blocks=self.num_blocks)
        self.requant_wall_s = 0.0       # dispatch time spent requantizing
        # fault injection (serving/faults.py): deterministic, seeded faults
        # at named sites; the injector may supply a virtual clock so
        # deadline scenarios replay bit-for-bit
        self.faults = faults
        self._clock = time.monotonic
        if faults is not None:
            if getattr(faults, "clock", None) is not None:
                self._clock = faults.clock
            if getattr(faults, "requant_hook", None) is not None:
                self.qmodel._fault_hook = faults.requant_hook
        # graceful-degradation ladder under sustained KV-pool pressure:
        # 0 = normal, 1 = speculation off, 2 = K=1 decode chunks,
        # 3 = cached prefix blocks dropped — all before preemption bites
        self.degrade_level = 0
        self.degrade_events = 0

    # ------------------------------------------------------------------- TTQ

    def _requantize(self):
        thr = self.ecfg.requant_threshold
        t0 = time.perf_counter()
        tree = self.qmodel.requantize(threshold=thr if thr >= 0 else None)
        self.requant_wall_s += time.perf_counter() - t0
        if tree is not None:
            self.scheduler.note_requant()

    # back-compat views of the parts' state (tests/benchmarks/examples)
    @property
    def decode_params(self):
        return self.qmodel.decode_params

    @property
    def draft_params(self):
        """The speculation draft tree (None when speculation is off)."""
        if self.ecfg.speculate_k <= 0:
            return None
        return self.qmodel.draft_params

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted drafts / drafted tokens across all speculation windows
        (EXPERIMENTS.md §"Self-speculative methodology")."""
        r = self.runner
        return r.spec_accepted / r.spec_drafted if r.spec_drafted else 0.0

    @property
    def spec_windows(self) -> int:
        return self.runner.spec_windows

    @property
    def qparams(self):
        return self.qmodel.qparams

    @property
    def n_requants(self):
        return self.qmodel.n_requants

    @property
    def lowrank_tree(self):
        return self.qmodel.lowrank_tree

    @property
    def layers_requantized(self):
        """Total leaf quantizations dispatched across all requants."""
        return self.qmodel.total_requant_layers

    @property
    def layers_skipped(self):
        """Total leaf quantizations the delta gate skipped (QT reused)."""
        return self.qmodel.total_skipped_layers

    @property
    def agg_stats(self):
        return self.qmodel.session.stats

    @property
    def stat_count(self):
        return self.qmodel.session.count

    @property
    def admits_since_cal(self):
        return self.scheduler.admits_since_cal

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def slot_req(self):
        return self.scheduler.slot_req

    @property
    def finished(self):
        return self.scheduler.finished

    @property
    def state(self):
        return self.runner.state

    @property
    def pos(self):
        return self.runner.pos

    @property
    def cur_tok(self):
        return self.runner.cur_tok

    @property
    def host_syncs(self):
        return self.runner.host_syncs

    @property
    def compiled_programs(self) -> int:
        """XLA programs resident across the engine's jit caches (decode,
        bucketed prefill, prefix gather, fused requant families).  Bounded
        by construction: decode compiles once, prefill once per
        (bucket, prefix_len, group_size) shape, requant once per family —
        tests/test_runtime_guards.py pins the bound and benchmarks gate on
        a zero steady-state delta (DESIGN.md §"Static analysis & runtime
        invariants")."""
        return (self.runner.compiled_programs
                + self.qmodel.compiled_programs
                + _guards.compiled_programs())

    # ------------------------------------------------- paged-pool metrics

    @property
    def allocator(self):
        """The paged pool's :class:`~repro.serving.blocks.BlockAllocator`
        (None on the dense slab)."""
        return self.scheduler.allocator

    @property
    def kv_pool_utilization(self) -> float:
        """Peak fraction of allocatable pool blocks ever in use."""
        a = self.allocator
        return a.peak_in_use / max(a.capacity, 1) if a else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        a = self.allocator
        return a.prefix_hit_rate() if a else 0.0

    @property
    def preemptions(self) -> int:
        return self.scheduler.preemptions

    @property
    def prefill_tokens(self) -> float:
        """Padded tokens dispatched to prefill (prefix hits shrink this)."""
        return self.scheduler.prefill_tokens

    # ------------------------------------- streaming / SLO telemetry (§13)

    @property
    def queue_depth(self) -> int:
        """Requests waiting in the intake queue right now."""
        return len(self.scheduler.queue)

    @property
    def queue_rejections(self) -> int:
        """Submits bounced off the ``max_queue`` capacity bound."""
        return self.scheduler.queue_rejections

    @property
    def prefill_chunks(self) -> int:
        """Chunked-prefill dispatches issued (0 with chunking off)."""
        return self.scheduler.prefill_chunks

    def latency_percentiles(self) -> Dict[str, float]:
        """p50/p99 time-to-first-token and inter-token latency (seconds,
        engine clock) over every request that has emitted tokens — finished
        and in-flight.  ``serve.py``'s summary and ``bench_serve_slo.py``
        both report from this one implementation, so the batch harness and
        the async server share a latency vocabulary."""
        reqs = list(self.scheduler.finished.values())
        reqs += [r for r in self.scheduler.slot_req if r is not None]
        ttfts, itls = [], []
        for r in reqs:
            ts = r.tok_times
            if not ts:
                continue
            ttfts.append(ts[0] - r.submit_t)
            itls += [b - a for a, b in zip(ts, ts[1:])]

        def pct(xs, q):
            if not xs:
                return 0.0
            s = sorted(xs)
            return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]

        return {"ttft_p50": pct(ttfts, 0.50), "ttft_p99": pct(ttfts, 0.99),
                "itl_p50": pct(itls, 0.50), "itl_p99": pct(itls, 0.99),
                "n_streams": len(ttfts), "n_itl": len(itls)}

    # -------------------------------------------- robustness telemetry (§12)

    @property
    def calib_rejections(self) -> int:
        """Calibration updates the session's guard quarantined (never
        folded into the running statistics)."""
        return self.qmodel.session.n_rejected

    @property
    def quarantine(self):
        """The session's bounded quarantine log (QuarantineRecord deque)."""
        return self.qmodel.session.quarantine

    @property
    def requant_rejections(self) -> int:
        """Candidate quantized trees the health gate refused to swap in."""
        return self.qmodel.requant_rejections

    @property
    def lane_faults(self) -> int:
        return self.scheduler.lane_faults

    @property
    def deadline_expirations(self) -> int:
        return self.scheduler.deadline_expirations

    @property
    def admission_failures(self) -> int:
        """Requests failed after exhausting the bounded admission-retry
        budget (``guard_cfg.max_admission_attempts``)."""
        return self.scheduler.admission_failures

    # --------------------------------------------------------------- serving

    def submit(self, prompt, max_new: int = 16, frames=None,
               deadline_s=None, priority: int = 0) -> int:
        """Queue a request; rejects prompts the engine cannot admit, and
        raises :class:`~repro.serving.scheduler.QueueFull` at
        ``EngineConfig.max_queue`` depth.

        ``deadline_s`` (seconds from now, 0 = none) bounds the request's
        wall-clock lifetime: expired requests — queued or running — are
        failed with ``error == "deadline"`` instead of occupying a lane
        forever.  Defaults to ``EngineConfig.deadline_s``.  ``priority``
        (lower = more urgent) picks the SLO class: admission order,
        eviction order and chunked-ingestion order all honour it
        (DESIGN.md §13)."""
        return self.scheduler.submit(prompt, max_new, frames=frames,
                                     deadline_s=deadline_s,
                                     now=self._clock(), priority=priority)

    def set_stream_callbacks(self, on_token=None, on_finish=None):
        """Install streaming callbacks: ``on_token(rid, tok, t)`` fires for
        every emitted token (first token included), ``on_finish(rid, req)``
        once per terminal landing (done, failed, cancelled, expired).
        Callbacks run on the engine-driving thread and must be cheap and
        device-free — the async server forwards into the event loop via
        ``call_soon_threadsafe`` (tracecheck TC407)."""
        self.scheduler.on_token = on_token
        self.scheduler.on_finish = on_finish

    def cancel(self, rid: int) -> bool:
        """Abort a queued or running request immediately: its slot and
        (paged) pool blocks free right away and its partial output is
        returned by ``results()`` flagged ``cancelled``.  Returns False if
        the rid is unknown or already finished."""
        ok = self.scheduler.cancel(rid)
        self._flush_releases()
        return ok

    def _flush_releases(self):
        """Deactivate slots the scheduler freed (finish / preempt / cancel)
        on device *before* their blocks can be reallocated."""
        slots = self.scheduler.pending_releases
        if slots:
            self.runner.release_slots(slots)
            self.scheduler.pending_releases = []

    def admit(self):
        """Admit queued requests into free slots: one batched prefill per
        bucket group, calibrate on its stats, requantize per cadence.

        Loops until the queue or the free slots run out: a request that
        finishes *at admission* (budget of 1, EOS or capacity on its first
        token) frees its slot immediately, and the next planning round hands
        that slot to the next queued request instead of stranding it."""
        while True:
            groups = self.scheduler.plan_admissions()
            self._flush_releases()   # preempted slots → sink before prefill
            if not groups:
                break
            for group in groups:
                # encdec frames ride each Request; the runner stages them
                # on device (the facade never allocates arrays)
                first, fin, stats = self.runner.admit_group(self.params,
                                                            group)
                rids = tuple(r.rid for r in group.requests)
                tokens = group.tokens
                if self.faults is not None:
                    stats, tokens = self.faults.calib_site(stats, tokens,
                                                           rids)
                if stats is not None:    # a "drop" fault skips the fold
                    self.qmodel.calibrate(stats, tokens=tokens,
                                          provenance=rids)
                self.scheduler.note_admitted(len(group.requests), group.tokens)
                now = self._clock()
                for i, (slot, req) in enumerate(zip(group.slots,
                                                    group.requests)):
                    self.scheduler.emit(req, int(first[i]), now)
                    if fin[i]:
                        self.scheduler.finish(slot)
        self._flush_releases()       # requests finished at admission
        if self.scheduler.should_requant():
            self._requantize()

    def _run_chunks(self):
        """Dispatch this round's chunked-prefill plans (DESIGN.md §13):
        at most ``prefill_budget`` padded tokens, most urgent ingestion
        first.  Each chunk folds its calibration statistics into the
        session — additive sufficient statistics, so the requant cadence
        sees the whole prompt across chunks exactly as it would from one
        monolithic prefill.  The final chunk arms the lane and emits the
        request's first token."""
        plans = self.scheduler.plan_prefill_chunks()
        for plan in plans:
            first, fin, stats = self.runner.prefill_chunk(self.params, plan)
            rids = (plan.req.rid,)
            tokens = float(self.ecfg.prefill_chunk)
            if self.faults is not None:
                stats, tokens = self.faults.calib_site(stats, tokens, rids)
            if stats is not None:        # a "drop" fault skips the fold
                self.qmodel.calibrate(stats, tokens=tokens, provenance=rids)
            self.scheduler.note_chunk(plan, float(self.ecfg.prefill_chunk))
            if plan.final:
                self.scheduler.emit(plan.req, int(first[0]), self._clock())
                if fin[0]:
                    self.scheduler.finish(plan.slot)
        if plans:
            self._flush_releases()   # finished-at-final-chunk slots → sink

    def _update_ladder(self):
        """Graceful-degradation ladder under KV-pool pressure (paged pool
        only).  Pressure = fraction of pool blocks currently allocated;
        above ``guard_cfg.degrade_pressure`` the engine climbs one rung,
        below ``recover_pressure`` it steps back down (hysteresis keeps it
        from flapping):

          0  normal service
          1  speculation off (draft tree unused — verify program only)
          2  decode chunk shrunk to K=1 (separate small jit, compiled
             lazily once)
          3  cached prefix blocks evicted back to the plain free list

        Each rung climbed bumps ``degrade_events``."""
        a = self.allocator
        gcfg = self.scheduler.gcfg
        if a is None or gcfg is None or not self.ecfg.guards:
            return
        pressure = 1.0 - len(a.free) / max(a.capacity, 1)
        if pressure >= gcfg.degrade_pressure and self.degrade_level < 3:
            self.degrade_level += 1
            self.degrade_events += 1
            if self.degrade_level >= 3:
                a.drop_cached()
        elif pressure <= gcfg.recover_pressure and self.degrade_level > 0:
            self.degrade_level -= 1

    def step(self) -> bool:
        """One engine iteration: expire deadlines, admit waiting requests,
        decode one fused block of ``decode_chunk`` tokens per active slot.

        Returns True while the engine still has work to drive — including
        rounds where every runnable request is waiting out a retry backoff
        (no decode dispatched, but ``run_all`` must keep stepping)."""
        now = self._clock()
        if self.faults is not None:
            self.faults.on_step(self)
        self.scheduler.expire_deadlines(now)
        self._flush_releases()       # deadline-evicted slots → sink
        self.admit()
        self._run_chunks()           # budgeted chunked-prefill dispatches
        self._update_ladder()
        if not self.scheduler.decode_slots():
            # mid-chunked-prefill lanes are work even though nothing decodes
            return (bool(self.scheduler.prefilling)
                    or self.scheduler.has_deferred_work())
        draft = None if self.degrade_level >= 1 else self.draft_params
        if self.faults is not None and self.runner.detect_faults:
            slots = self.faults.decode_site(self.scheduler.slot_req,
                                            self.scheduler._round)
            self.runner.set_poison(slots)
        toks, valid, done, fault = self.runner.decode_block(
            self.decode_params, draft, small_chunk=self.degrade_level >= 2)
        self.scheduler.record_block(toks, valid, done, fault=fault,
                                    now=self._clock())
        self._flush_releases()       # freed blocks must not be written again
        if self.scheduler.should_requant():
            self._requantize()
        return True

    def run_all(self, max_iters: int = 10_000) -> Dict[int, GenResult]:
        """Drive until all submitted requests finish; returns {rid: tokens}.

        Hitting ``max_iters`` no longer drops in-flight work: partial
        outputs are returned with ``result.unfinished == True``."""
        it = 0
        while self.scheduler.has_work() and it < max_iters:
            if not self.step():
                break
            it += 1
        return self.scheduler.results()
