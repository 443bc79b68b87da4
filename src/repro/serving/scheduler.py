"""Scheduler — the host half of the serving engine: requests and policy.

Owns everything that is bookkeeping rather than device math: the request
queue, the slot table, admission planning (free slots are filled in
priority/deadline order, then the round's admissions are grouped by padded
prompt bucket so each group is ONE batched prefill dispatch), the chunked
prefill ledger, and the requantization cadence.

SLO scheduling (DESIGN.md §13): requests carry a priority class (lower =
more urgent) and an optional deadline; admission picks by
``(priority, absolute deadline, submission order)`` — earliest-deadline-
first within a class, FIFO when neither priority nor deadlines are set.
Preemption (pool pressure) victims are picked from the *least* important
class first, and a request never evicts a more important one.  Long
prompts are ingested in fixed-size chunks (``EngineConfig.prefill_chunk``)
interleaved with decode rounds under a per-round padded-token budget
(``prefill_budget``) so a 4k-token arrival cannot monopolize a dispatch
round and blow up running streams' inter-token latency.

Cadence is a policy, not a side effect of admission (the paper's Fig. 1b
lifecycle): with ``EngineConfig.recalibrate_tokens > 0`` the engine
requantizes once the token budget (prefill + generated tokens since the last
requant) is exhausted *and* fresh statistics have arrived; otherwise it
falls back to the per-admission counter (``recalibrate_every``).

No jax arrays live here — the device side is :class:`~repro.serving.runner.
DeviceRunner` and the two are composed by :class:`~repro.serving.engine.
TTQEngine`.  That array-free contract (tracecheck TC402/TC405) is also what
makes the scheduler mesh-oblivious: on a sharded engine (DESIGN.md §10) the
same queue/slot/cadence decisions drive every device — admission groups,
block budgets and requant cadence are global properties, and the runner
replays them against the sharded state.
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from .telemetry import Telemetry


class QueueFull(RuntimeError):
    """``submit`` rejected: the intake queue is at ``max_queue`` capacity.

    The synchronous engine surfaces this to the caller (shed load / retry
    later); the async front end (:class:`~repro.serving.server.TTQServer`)
    holds its own admission semaphore so coroutines *await* instead."""


def pick_decode_chunk(slots: int, speculate_k: int = 0) -> int:
    """Default fused-decode chunk per slot count (EXPERIMENTS.md §Perf
    iteration 7).  At 1 slot fused decode at K=8 measured *slower* than
    per-token on short generation budgets (fixed-K steps are wasted past
    EOS/budget — the PR-3 snapshot: 165 vs 724 tok/s at max_new=16), and
    there is no batching to amortize, so stay per-token; from 2 slots up
    the dispatch amortization dominates for every measured budget and K=8
    sits past the crossover (`bench_engine.py` sweeps K and reports it).

    With self-speculative decoding (DESIGN.md §11) the chunk counts
    *windows*, and each window emits up to ``speculate_k + 1`` tokens per
    lane — the effective tokens/dispatch is ``chunk × (W+1) × acceptance``.
    To keep the wasted-work exposure past EOS/budget comparable to the
    non-speculative tuning above, divide the chunk by the per-window token
    ceiling (floor 1); the 1-slot case stays per-window for the same
    crossover reason it stays per-token without speculation."""
    base = 1 if slots <= 1 else 8
    if speculate_k <= 0:
        return base
    return max(1, base // (speculate_k + 1))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list                    # grows on preemption: orig + generated
    max_new: int                    # ORIGINAL budget; remaining = max_new - len(out)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    frames: Any = None              # encdec stub modality input
    cancelled: bool = False
    blocks: list = dataclasses.field(default_factory=list)  # paged: owned blocks
    prefix_len: int = 0             # paged: cached-prefix tokens this admission
    admit_seq: int = -1             # admission order (preemption victim pick)
    orig_len: int = 0               # submitted prompt length (pre-preemption)
    # ---- isolation / deadlines (DESIGN.md §12) ----
    deadline_s: float = 0.0         # wall budget from submit (0 = none)
    submit_t: float = 0.0           # engine-clock submission time
    error: str = ""                 # terminal failure reason ("" = none)
    attempts: int = 0               # decode-fault retries consumed
    not_before: int = 0             # planning round gating a retry (backoff)
    # ---- SLO / streaming (DESIGN.md §13) ----
    priority: int = 0               # class: lower = more urgent
    prefilled: int = 0              # chunked prefill: tokens resident on device
    tok_times: list = dataclasses.field(default_factory=list)  # emit stamps
    # ---- lifecycle stamps (engine clock; serving/telemetry.py) ----
    recv_t: float = 0.0             # received by the front end (≤ submit_t)
    admit_t: float = 0.0            # slot claimed (the latest claim)
    first_t: Optional[float] = None  # first token ever (kept across retries)

    def __post_init__(self):
        if not self.orig_len:
            self.orig_len = len(self.prompt)

    @property
    def remaining(self) -> int:
        return self.max_new - len(self.out)


class GenResult(list):
    """A request's generated tokens.  Compares and prints as a plain list;
    ``unfinished`` marks a partial output (the engine stopped at
    ``max_iters`` with the request still queued or mid-generation, the
    request was cancelled — ``cancelled`` distinguishes that — or it failed
    terminally, in which case ``error`` carries the reason: "deadline",
    "non-finite logits", "admission retries exhausted")."""

    def __init__(self, tokens=(), unfinished: bool = False,
                 cancelled: bool = False, error: str = ""):
        super().__init__(tokens)
        self.unfinished = unfinished
        self.cancelled = cancelled
        self.error = error


@dataclasses.dataclass
class AdmissionGroup:
    """One bucketed prefill dispatch: requests padded to a shared length.

    Paged prefix-cache hits carry a nonzero ``prefix_len`` — the bucket then
    pads the prompt *tail* and the prefill attends to the cached prefix."""
    bucket: int
    prefix_len: int = 0
    slots: List[int] = dataclasses.field(default_factory=list)
    requests: List[Request] = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> float:
        return float(len(self.requests) * self.bucket)


@dataclasses.dataclass
class ChunkPlan:
    """One chunked-prefill dispatch for one mid-ingestion request: write
    prompt rows ``[start, start + length)`` into the slot's cache (padded to
    ``prefill_chunk``).  The ``final`` chunk runs the admission epilogue —
    sample the first token and arm the lane for decode."""
    slot: int
    req: Request
    start: int                      # tokens already resident (prefix + chunks)
    length: int                     # real tokens this chunk (<= prefill_chunk)
    final: bool


class Scheduler:
    def __init__(self, ecfg, exact_buckets: bool = False, kvcfg=None,
                 num_blocks: int = 0, telemetry: Optional[Telemetry] = None):
        self.ecfg = ecfg
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        # recurrent state would absorb pad tokens — prefill at exact length
        self.exact_buckets = exact_buckets
        self.queue: deque = deque()
        self.slot_req: List[Optional[Request]] = [None] * ecfg.max_slots
        self.finished: Dict[int, Request] = {}
        self._rid = itertools.count()
        self.admits_since_cal = 0
        self.tokens_since_cal = 0.0
        self._fresh_stats = False
        # paged pool bookkeeping (DESIGN.md §8)
        self.allocator = None
        if kvcfg is not None and getattr(kvcfg, "paged", False):
            from .blocks import BlockAllocator
            self.allocator = BlockAllocator(
                num_blocks, kvcfg.block_size,
                prefix_cache=getattr(ecfg, "prefix_cache", True))
        self._admit_seq = itertools.count()
        self.prefill_tokens = 0.0       # padded tokens dispatched to prefill
        self.preemptions = 0
        self.pending_releases: List[int] = []   # slots to sink on device
        self._recent_victims: set = set()       # no re-preemption until decode
        # isolation / robustness counters (DESIGN.md §12).  The guard knobs
        # (retry budget, admission-attempt cap) apply regardless of
        # EngineConfig.guards — the flag gates *detection* machinery, not
        # plain bookkeeping like capping a retry loop.
        self.gcfg = getattr(ecfg, "guard_cfg", None)
        self.lane_faults = 0            # decode lanes failed on bad logits
        self.deadline_expirations = 0
        self.admission_failures = 0     # requests failed at the attempt cap
        self._round = 0                 # planning rounds (retry backoff unit)
        self._starve: Dict[int, int] = {}   # rid → idle-starved rounds
        # SLO / streaming (DESIGN.md §13)
        self.prefilling: Dict[int, Request] = {}  # slot → mid-chunked-prefill
        self.prefill_chunks = 0         # chunk dispatches (telemetry)
        self.queue_rejections = 0       # submits bounced off max_queue
        self.on_token: Optional[Callable] = None    # (rid, tok, now)
        self.on_finish: Optional[Callable] = None   # (rid, req)

    # ---------------------------------------------------------------- intake

    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: the cache must hold it and (for
        bucketed families) the largest bucket must fit it.  Chunked prefill
        lifts the bucket limit — any prompt the cache holds can be ingested
        chunk by chunk."""
        if self.exact_buckets or getattr(self.ecfg, "prefill_chunk", 0) > 0:
            return self.ecfg.max_len
        return min(max(self.ecfg.prompt_buckets), self.ecfg.max_len)

    def submit(self, prompt, max_new: int = 16, frames=None,
               deadline_s: Optional[float] = None, now: float = 0.0,
               priority: int = 0, recv_t: Optional[float] = None) -> int:
        prompt = list(prompt)
        limit = self.max_prompt_len
        if len(prompt) > limit:
            detail = f"max_len={self.ecfg.max_len}"
            if limit != self.ecfg.max_len:
                detail += (f", largest prompt bucket "
                           f"{max(self.ecfg.prompt_buckets)}")
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the engine's "
                f"admissible length {limit} ({detail}); raise max_len / "
                f"prompt_buckets or truncate the prompt")
        mq = getattr(self.ecfg, "max_queue", 0)
        if mq and len(self.queue) >= mq:
            self.queue_rejections += 1
            raise QueueFull(
                f"intake queue at capacity (max_queue={mq}); shed load or "
                f"retry after the engine drains")
        if self.allocator is not None:
            need = self.allocator.blocks_needed(len(prompt), max_new,
                                                self.ecfg.max_len)
            if need > self.allocator.capacity:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self.allocator.capacity}; raise kv_pool_blocks or "
                    f"shrink the prompt/max_new")
        rid = next(self._rid)
        dl = float(getattr(self.ecfg, "deadline_s", 0.0)
                   if deadline_s is None else deadline_s)
        self.queue.append(Request(rid, prompt, max_new, frames=frames,
                                  deadline_s=dl, submit_t=float(now),
                                  priority=int(priority),
                                  recv_t=float(now if recv_t is None
                                               else recv_t)))
        return rid

    # ------------------------------------------------------------- streaming

    def emit(self, req: Request, tok: int, now: float = 0.0):
        """Land one generated token: append to the request's output, stamp
        the emission time (TTFT/ITL metrics) and fire the streaming
        callback.  Every token-producing path funnels through here so
        ``len(out) == len(tok_times)`` holds everywhere.  A request's first
        token ever splits its wait in the telemetry — once, though a
        lane-fault retry emits from the start again."""
        if req.first_t is None:
            req.first_t = float(now)
            self.telemetry.first_token(req.recv_t, req.submit_t, req.admit_t,
                                       req.first_t)
        req.out.append(int(tok))
        req.tok_times.append(float(now))
        if self.on_token is not None:
            self.on_token(req.rid, int(tok), float(now))

    def _land(self, req: Request):
        """Terminal landing: the request is finished (done, failed,
        cancelled, expired) — record it and fire the completion callback."""
        self.finished[req.rid] = req
        if self.on_finish is not None:
            self.on_finish(req.rid, req)

    # ------------------------------------------------------------- admission

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def decode_slots(self) -> List[int]:
        """Slots with an armed decode lane — active minus mid-chunked-
        prefill (those are parked ``done`` on device until their final
        chunk lands)."""
        return [s for s in self.active_slots() if s not in self.prefilling]

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def bucket(self, n: int) -> int:
        if self.exact_buckets:
            return n
        for b in self.ecfg.prompt_buckets:
            if n <= b:
                return min(b, self.ecfg.max_len)
        # beyond the largest bucket: only reachable by preemption-resumed
        # prompts (submit() rejects external ones) — pad to max_len
        return self.ecfg.max_len

    # ----------------------------------------------- isolation (DESIGN.md §12)

    def _evict(self, slot: int, req: Request, finished: bool):
        """Shared failure-path eviction: clear the slot, free (paged)
        blocks, queue the device release; optionally land in finished."""
        self.slot_req[slot] = None
        self.prefilling.pop(slot, None)
        if self.allocator is not None:
            self.allocator.free_request(req.blocks)
            req.blocks = []
        self.pending_releases.append(slot)
        if finished:
            self._land(req)

    def fail_lane(self, slot: int, reason: str):
        """A decode lane went bad (non-finite logits): fail ONLY this
        request — slot recycled, blocks released, the rest of the batch
        untouched.  Within the retry budget the request requeues from its
        original prompt with exponential backoff in planning rounds (the
        fault may be load-coupled — give the batch time to drain); past it
        the request finishes with ``error=reason``."""
        req = self.slot_req[slot]
        self.lane_faults += 1
        max_retries = self.gcfg.max_retries if self.gcfg is not None else 0
        if req.attempts < max_retries:
            req.attempts += 1
            self._evict(slot, req, finished=False)
            req.prompt = list(req.prompt[:req.orig_len])
            req.out = []
            req.tok_times = []
            req.prefix_len = 0
            req.prefilled = 0
            req.not_before = self._round + (1 << req.attempts)
            self.queue.append(req)
        else:
            req.error = reason
            self._evict(slot, req, finished=True)

    def expire_deadlines(self, now: float):
        """Fail queued and running requests past their ``deadline_s`` (no
        retry — the clock that expired them keeps running).  Running
        requests keep their partial output."""
        for req in [r for r in self.queue
                    if r.deadline_s > 0 and now - r.submit_t > r.deadline_s]:
            self.queue.remove(req)
            req.error = "deadline"
            self._land(req)
            self.deadline_expirations += 1
        for slot, req in enumerate(self.slot_req):
            if (req is not None and req.deadline_s > 0
                    and now - req.submit_t > req.deadline_s):
                req.error = "deadline"
                self._evict(slot, req, finished=True)
                self.deadline_expirations += 1

    def has_deferred_work(self) -> bool:
        """Queued work the engine must keep stepping for even though no
        lane is active: retries whose backoff round has not arrived, and
        requests waiting out a *transient* pool starvation (idle lanes +
        an allocation that keeps failing — e.g. injected exhaustion).
        Both are bounded: backoff by the retry budget, starvation by the
        admission-attempt cap — so ``run_all`` can never spin forever."""
        return (any(r.not_before > self._round for r in self.queue)
                or any(r.rid in self._starve for r in self.queue))

    # ------------------------------------------------------------ preemption

    def _pick_victim(self, exclude, limit_priority: int = 0
                     ) -> Optional[int]:
        """Class-based eviction: the least important running class loses
        first (highest priority number), youngest admission within it (FIFO
        keeps older work running; its resume re-prefill is cheap anyway
        because its own prompt blocks stay in the prefix cache).  A request
        never evicts a lane *more* important than itself
        (``victim.priority >= limit_priority``) — equal-class preemption
        stays allowed so a full pool of peers behaves exactly as before
        priorities existed."""
        cands = [(self.slot_req[s].priority, self.slot_req[s].admit_seq, s)
                 for s in self.active_slots()
                 if s not in exclude
                 and self.slot_req[s].priority >= limit_priority]
        return max(cands)[2] if cands else None

    def _preempt(self, slot: int) -> Request:
        """Evict a running slot: free its blocks and fold the generated
        tokens into the prompt — a later re-prefill resumes the greedy
        stream exactly (per-token quantization makes re-prefilled rows
        identical to the evicted ones; the resume footprint stays constant:
        ``len(prompt) + remaining == orig_len + max_new``).  The caller
        requeues the returned request once the round's planning is done."""
        req = self.slot_req[slot]
        self.allocator.free_request(req.blocks)
        req.blocks = []
        req.prompt = list(req.prompt[:req.orig_len]) + list(req.out)
        req.prefilled = 0               # mid-chunked-prefill victims restart
        self.slot_req[slot] = None
        self.prefilling.pop(slot, None)
        self.pending_releases.append(slot)
        self.preemptions += 1
        self._recent_victims.add(req.rid)
        return req

    def plan_admissions(self, now: float = 0.0) -> List[AdmissionGroup]:
        """Pop queued requests into free slots in FIFO order, then group the
        round's admissions by (bucket, prefix_len) — each group is one
        prefill dispatch.  ``now`` (engine clock) stamps each claim.

        Paged: each admission reserves its blocks upfront (prompt +
        generation budget, minus prefix-cache hits).  On pool exhaustion a
        running slot is preempted (blocks freed, its slot handed to the
        admission) instead of stalling; victims are held out of the queue
        until planning ends, then requeued at the front — they resume via
        re-prefill (their own blocks stay prefix-cached), never in the same
        round they were evicted.

        The MemoryError→preempt→retry loop is bounded per request per round
        (``guard_cfg.max_admission_attempts``, lifted to at least
        ``max_slots + 1`` so a legitimate chain that preempts every running
        slot still fits): a pathological allocation — one that keeps
        raising after its victims freed their blocks — fails the request
        cleanly (``error="admission retries exhausted"``) instead of
        spinning planning forever.  Requests whose retry backoff round has
        not arrived (``not_before``) are skipped, not popped.

        SLO ordering (DESIGN.md §13): the next admission is the eligible
        request minimizing ``(priority, absolute deadline, rid)`` —
        priority classes strictly dominate, earliest deadline first within
        a class, FIFO among undeadlined peers.  Eviction honours the same
        classes via :meth:`_pick_victim`.  Requests whose prompt tail
        exceeds ``prefill_chunk`` claim their slot and blocks here but skip
        the group dispatch — they enter the ``prefilling`` ledger and are
        ingested chunk-by-chunk by :meth:`plan_prefill_chunks`; their lane
        is parked on device (queued slot release) until the final chunk
        arms it, and their fresh blocks enter the prefix trie only as the
        rows land (``allocate(register=False)``)."""
        self._round += 1
        cap = self.gcfg.max_admission_attempts if self.gcfg is not None else 8
        cap = max(cap, self.ecfg.max_slots + 1)
        attempts: Dict[int, int] = {}
        picked: List[tuple] = []
        victims: List[Request] = []
        free = self.free_slots()
        while free:
            req = min((r for r in self.queue
                       if r.not_before <= self._round),
                      key=self._sel_key, default=None)
            if req is None:
                break
            if self.allocator is not None:
                try:
                    req.blocks, req.prefix_len = self.allocator.allocate(
                        req.prompt, req.remaining, self.ecfg.max_len,
                        register=not self._maybe_chunked(req))
                except MemoryError:
                    attempts[req.rid] = attempts.get(req.rid, 0) + 1
                    if attempts[req.rid] >= cap:
                        self.queue.remove(req)
                        self._starve.pop(req.rid, None)
                        req.error = "admission retries exhausted"
                        self._land(req)
                        self.admission_failures += 1
                        continue            # next eligible request
                    victim = self._pick_victim(
                        exclude={s for s, _ in picked},
                        limit_priority=req.priority)
                    # a fresh victim may not preempt in turn until decode
                    # has progressed — breaks admit-round ping-pong cycles
                    if victim is None or req.rid in self._recent_victims:
                        if not self.active_slots() and not picked:
                            # idle starvation: the pool is short with no
                            # lane running to free it (transient theft or
                            # a leak).  Wait a bounded number of rounds —
                            # has_deferred_work() keeps the engine
                            # stepping — then fail the request cleanly.
                            n = self._starve.get(req.rid, 0) + 1
                            self._starve[req.rid] = n
                            if n >= cap:
                                self.queue.remove(req)
                                self._starve.pop(req.rid, None)
                                req.error = "admission retries exhausted"
                                self._land(req)
                                self.admission_failures += 1
                        break               # nothing evictable — wait
                    victims.append(self._preempt(victim))
                    free = self.free_slots()
                    continue                # retry with the freed blocks
            self._starve.pop(req.rid, None)
            self.queue.remove(req)
            req.admit_seq = next(self._admit_seq)
            req.admit_t = float(now)
            slot = free.pop(0)
            self.slot_req[slot] = req       # claimed now: a preemption later
            picked.append((slot, req))      # in this round must not free it
            if self._chunked(req):
                req.prefilled = req.prefix_len
                self.prefilling[slot] = req
                self.pending_releases.append(slot)  # park the lane on device
            elif self.allocator is not None and self._maybe_chunked(req):
                # prefix hits shrank the tail under one chunk — classic
                # dispatch after all; hook the deferred registrations now
                # (identical to allocate(register=True) semantics)
                self.allocator.register_blocks(req.prompt, req.blocks,
                                               len(req.prompt))
        for req in reversed(victims):       # oldest victim resumes first
            self.queue.appendleft(req)
        groups: Dict[tuple, AdmissionGroup] = {}
        for slot, req in picked:
            if slot in self.prefilling:     # chunk-ingested, no group
                continue
            tail = len(req.prompt) - req.prefix_len
            key = (self.bucket(tail), req.prefix_len)
            g = groups.setdefault(key, AdmissionGroup(*key))
            g.slots.append(slot)
            g.requests.append(req)
        # dispatch order = ascending prefix_len: a same-round prefix hit on
        # a sibling's freshly registered blocks always reads blocks the
        # *writer* prefills, and along one hash chain the reader's match
        # necessarily extends past the writer's own prefix — reader
        # prefix_len > writer prefix_len.  Sorting is therefore a
        # topological order of same-round dependencies: every group's
        # gather dispatches after the scatters it reads (equal prefix_len
        # ⇒ no dependency).  Without it a reader could share a group
        # created before its writer's and gather still-zero pool blocks.
        return sorted(groups.values(), key=lambda g: g.prefix_len)

    @staticmethod
    def _sel_key(req: Request):
        """Admission order: priority class, then earliest absolute
        deadline, then submission (rid) — plain FIFO when neither knob is
        used."""
        dl = (req.submit_t + req.deadline_s if req.deadline_s > 0
              else float("inf"))
        return (req.priority, dl, req.rid)

    def _maybe_chunked(self, req: Request) -> bool:
        """Could this request need chunked ingestion?  Decided before the
        prefix match — used to defer trie registration."""
        c = getattr(self.ecfg, "prefill_chunk", 0)
        return c > 0 and len(req.prompt) > c

    def _chunked(self, req: Request) -> bool:
        """Chunked ingestion needed: the un-cached prompt tail exceeds one
        chunk (prefix hits may have shrunk it under the threshold)."""
        c = getattr(self.ecfg, "prefill_chunk", 0)
        return c > 0 and (len(req.prompt) - req.prefix_len) > c

    # ------------------------------------------------------- chunked prefill

    def plan_prefill_chunks(self) -> List[ChunkPlan]:
        """The round's chunk dispatches, most urgent request first, capped
        at ``prefill_budget`` padded tokens (default: one chunk per round —
        decode runs between every pair of chunks).  Always yields at least
        one chunk when ingestion is pending, so a sub-chunk budget cannot
        stall a prompt forever.  Plans are speculative until the engine
        lands them via :meth:`note_chunk`."""
        if not self.prefilling:
            return []
        chunk = self.ecfg.prefill_chunk
        budget = getattr(self.ecfg, "prefill_budget", 0) or chunk
        plans: List[ChunkPlan] = []
        spent = 0
        for slot, req in sorted(self.prefilling.items(),
                                key=lambda kv: self._sel_key(kv[1])):
            plen, prog = len(req.prompt), req.prefilled
            while prog < plen and (spent < budget or not plans):
                n = min(chunk, plen - prog)
                plans.append(ChunkPlan(slot, req, prog, n,
                                       final=prog + n >= plen))
                prog += n
                spent += chunk          # budget counts padded tokens
            if spent >= budget:
                break
        return plans

    def note_chunk(self, plan: ChunkPlan, tokens: float):
        """One chunk landed on device: advance the resident-token mark,
        expose the freshly written full blocks to the prefix trie, and fold
        the (padded) chunk into the requant cadence.  The final chunk
        counts as the admission and un-parks the ledger entry — the engine
        arms the lane and emits the first token."""
        req = plan.req
        req.prefilled = plan.start + plan.length
        if self.allocator is not None:
            self.allocator.register_blocks(req.prompt, req.blocks,
                                           req.prefilled)
        self.prefill_chunks += 1
        self.note_admitted(1 if plan.final else 0, tokens)
        if plan.final:
            self.prefilling.pop(plan.slot, None)

    # -------------------------------------------------------- requant cadence

    def note_admitted(self, n: int, tokens: float):
        """n requests prefilled (fresh statistics folded into the session)."""
        self.admits_since_cal += n
        self.tokens_since_cal += tokens
        self.prefill_tokens += tokens
        self._fresh_stats = True

    def note_decoded(self, tokens: int):
        self.tokens_since_cal += tokens
        self._recent_victims.clear()    # decode progressed — preemption rearmed

    def should_requant(self) -> bool:
        if self.ecfg.recalibrate_tokens > 0:
            return (self._fresh_stats
                    and self.tokens_since_cal >= self.ecfg.recalibrate_tokens)
        return self.admits_since_cal >= self.ecfg.recalibrate_every

    def note_requant(self):
        self.admits_since_cal = 0
        self.tokens_since_cal = 0.0
        self._fresh_stats = False

    # --------------------------------------------------------------- results

    def finish(self, slot: int):
        req = self.slot_req[slot]
        req.done = True
        self.slot_req[slot] = None
        if self.allocator is not None:
            self.allocator.free_request(req.blocks)
            req.blocks = []
            self.pending_releases.append(slot)
        self._land(req)

    def cancel(self, rid: int) -> bool:
        """Abort a queued or running request: its slot and (paged) blocks
        free immediately — including blocks partially written by chunked
        prefill — and the partial output lands in ``finished`` as
        ``cancelled`` (``results()`` flags it unfinished).  Returns False
        for unknown/already-finished rids."""
        for req in list(self.queue):
            if req.rid == rid:
                self.queue.remove(req)
                req.cancelled = True
                self._land(req)
                return True
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.rid == rid:
                req.cancelled = True
                self.slot_req[slot] = None
                self.prefilling.pop(slot, None)
                if self.allocator is not None:
                    self.allocator.free_request(req.blocks)
                    req.blocks = []
                self.pending_releases.append(slot)
                self._land(req)
                return True
        return False

    def record_block(self, tokens, valid, done, fault=None,
                     now: float = 0.0) -> int:
        """Fold one decode block's host copies into per-request outputs.

        ``tokens``/``valid``: (B, K) host arrays; ``done``: (B,) final
        flags; ``fault``: optional (B,) lane-fault flags from the guarded
        decode (DESIGN.md §12) — a faulted lane's block is discarded
        wholesale (its logits are suspect from the start of the block) and
        the request fails alone via :meth:`fail_lane`.  Mid-chunked-prefill
        slots are skipped: their lanes are parked ``done`` on device, which
        must not be mistaken for EOS.
        Returns the number of accepted tokens (token-budget cadence)."""
        accepted = 0
        K = tokens.shape[1]
        for slot in self.active_slots():
            if slot in self.prefilling:
                continue
            req = self.slot_req[slot]
            if fault is not None and fault[slot]:
                self.fail_lane(slot, "non-finite logits")
                continue
            for k in range(K):
                if valid[slot, k]:
                    self.emit(req, int(tokens[slot, k]), now)
                    accepted += 1
            if done[slot]:
                self.finish(slot)
        self.note_decoded(accepted)
        return accepted

    def results(self, include_partials: bool = True) -> Dict[int, GenResult]:
        """Finished outputs, plus (by default) in-flight/queued partials
        flagged ``unfinished=True`` — nothing submitted is silently dropped.
        Cancelled requests report their partial output with both flags."""
        out = {rid: GenResult(req.out,
                              unfinished=req.cancelled or bool(req.error),
                              cancelled=req.cancelled, error=req.error)
               for rid, req in self.finished.items()}
        if include_partials:
            pending = [r for r in self.slot_req if r is not None]
            pending += list(self.queue)
            for req in pending:
                out[req.rid] = GenResult(req.out, unfinished=True)
        return out
