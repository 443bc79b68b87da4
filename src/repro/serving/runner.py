"""DeviceRunner — the jitted device half of the serving engine.

Owns the batched decode state (slot caches, positions, per-slot done flags
and generation budgets) plus the two compiled programs:

* a bucketed batched prefill — one dispatch per admission group with the
  stats tap on, instead of B=1 sequential prefills;
* ``lm.decode_many`` — a ``lax.scan`` over ``decode_chunk`` decode steps
  with on-device sampling / EOS / budget / capacity masking, so the host
  sees ONE blocking transfer per chunk (a (B, K) token block + flags)
  instead of one per token per slot.  The engine's ``KernelConfig``
  (``kncfg``) is baked into this program as a static arg: with
  ``use_pallas=True`` every packed-weight matmul inside the scan dispatches
  the fused Pallas ``ttq_gemm``.

With a **paged** ``KVCacheConfig`` (DESIGN.md §8) the slot caches become
per-layer block pools plus a per-slot ``block_table``; admission scatters
the prefill's compact k/v into the slots' physical blocks (prefix-cache
hits prefill only the prompt *tail*, gathering the cached prefix from the
pool), and ``release_slots`` points finished/preempted slots at the sink
block 0 so their done-lane writes can never corrupt reallocated blocks.

``host_syncs`` counts blocking device→host transfers — the number
``benchmarks/bench_engine.py`` reports per generated token.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.quant.api import _path_str

from .blocks import SINK
from .sampling import sample


def _write_slots(batched, src, slots):
    """Write the rows of a batch-``n`` prefill state into slots ``slots`` of
    the batched decode state (stack leaves carry (R, B, ...); other leaves
    (B, ...)) — codes and scales alike for quantized cache layouts."""
    idx = jnp.asarray(slots, jnp.int32)

    def per(path, bl, sl):
        if _path_str(path).startswith("stack"):
            return bl.at[:, idx].set(sl.astype(bl.dtype))
        return bl.at[idx].set(sl.astype(bl.dtype))

    return jax.tree_util.tree_map_with_path(per, batched, src)


def _write_paged(pools, compact, phys, block_size: int):
    """Scatter a compact prefill state into the paged pools.

    pools: per-run {'u0': {leaf: (R, NB, Hkv, bs, D·)}};
    compact: same structure with (R, n, Hkv, Sb, D·) leaves (Sb = the
    group's padded tail bucket); phys: (n, nbw) int32 physical block per
    logical write block — pad blocks beyond the prompt point at the sink.
    """
    bs = block_size
    nbw = phys.shape[1]

    def per(pool, cl):
        R, n, Hkv, Sb, D = cl.shape
        pad = nbw * bs - Sb
        if pad:
            cl = jnp.pad(cl, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        blk = cl.reshape(R, n, Hkv, nbw, bs, D).transpose(0, 1, 3, 2, 4, 5)
        return pool.at[:, phys].set(blk.astype(pool.dtype))

    return jax.tree.map(per, pools, compact)


def _write_rows(batched, compact, slot: int, start: int, max_len: int):
    """Write a compact chunk state into rows ``[start, start + L)`` of one
    slot of the dense batched slab (chunked prefill, DESIGN.md §13).

    batched: per-run {'u0': {leaf: (R, B, Hkv, ML, ·)}}; compact: same
    structure with (R, 1, Hkv, C, ·) leaves.  ``L = min(C, max_len -
    start)`` — the final chunk's pad columns may overhang the slab; the
    dropped overhang holds pad garbage by construction.  Eager
    ``dynamic_update_slice`` with host-static offsets: no advanced-index
    normalization, no h2d."""
    def per(bl, cl):
        L = min(cl.shape[3], max_len - start)
        return jax.lax.dynamic_update_slice(
            bl, cl[:, :, :, :L].astype(bl.dtype), (0, slot, 0, start, 0))

    return jax.tree.map(per, batched, compact)


def _gather_pool(pool, ptab):
    """pool (R, NB, Hkv, bs, D·) + ptab (n, nbp) → (R, n, Hkv, nbp·bs, D·):
    the oracle's per-slot gather, vmapped over the leading layer dim so the
    two layouts can never drift apart."""
    from repro.kernels.ref import gather_paged_kv

    return jax.vmap(lambda p: gather_paged_kv(p, ptab))(pool)


class DeviceRunner:
    def __init__(self, cfg, ecfg, kvcfg, *, kncfg=None, pctx=None, key=None,
                 num_blocks: int = 0):
        self.cfg, self.ecfg, self.kvcfg, self.pctx = cfg, ecfg, kvcfg, pctx
        self.kncfg = kncfg                      # KernelConfig: packed-weight
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.paged = kvcfg is not None and kvcfg.paged
        self.num_blocks = num_blocks
        B, ML = ecfg.max_slots, ecfg.max_len
        K = max(1, ecfg.decode_chunk)           # 0 = auto, resolved upstream
        self.state = lm.init_decode_state(cfg, B, ML, kvcfg=kvcfg,
                                          num_blocks=num_blocks)
        self.pos = jnp.zeros((B,), jnp.int32)
        self.cur_tok = jnp.zeros((B, 1), jnp.int32)
        self.done = jnp.ones((B,), bool)        # empty slot = done lane
        self.remaining = jnp.zeros((B,), jnp.int32)
        self.host_syncs = 0                     # blocking device→host copies
        # fault isolation (DESIGN.md §12): with guards on, decode checks
        # per-step logit finiteness on device and reports a per-slot fault
        # mask; the poison lane is the deterministic injection site
        # (serving/faults.py) — all-False outside fault-injection runs
        self.detect_faults = bool(getattr(ecfg, "guards", False))
        self._poison = jnp.zeros((B,), bool) if self.detect_faults else None
        # device-resident constants so steady-state lane updates stay free of
        # implicit host→device transfers (jax.transfer_guard("disallow")
        # clean — see tests/test_runtime_guards.py)
        self._zero = jnp.asarray(0, jnp.int32)
        self._sink = jnp.asarray(SINK, jnp.int32)
        self._maxlen = jnp.asarray(ML, jnp.int32)
        # mesh serving: commit the decode state to its canonical layout (KV
        # heads on the model axis; paged pools shard heads, never blocks) and
        # the scalar lanes replicated.  The shardings are cached so admission
        # epilogues can re-pin — the decode jit must only ever see ONE
        # input-sharding signature (DESIGN.md §"Mesh-sharded serving").
        if pctx is not None and pctx.mesh is not None:
            from repro.parallel.rules import state_sharding
            self._state_shardings = state_sharding(self.state, pctx,
                                                   paged=self.paged)
            self._rep = jax.sharding.NamedSharding(
                pctx.mesh, jax.sharding.PartitionSpec())
            self.state = jax.tree.map(jax.device_put, self.state,
                                      self._state_shardings)
            self._zero = jax.device_put(self._zero, self._rep)
            self._sink = jax.device_put(self._sink, self._rep)
            self._maxlen = jax.device_put(self._maxlen, self._rep)
            if self._poison is not None:
                self._poison = jax.device_put(self._poison, self._rep)
        else:
            self._state_shardings = None
            self._rep = None
        self._repin()
        out_kw = {}
        if self._state_shardings is not None:
            rep = self._rep
            ys = (rep, rep, rep) if self.detect_faults else (rep, rep)
            out_kw["out_shardings"] = (ys,
                                       (self._state_shardings,
                                        rep, rep, rep, rep, rep))
        self._out_kw = out_kw
        self._decode_jit = self._decode_program(K)
        # degradation ladder rung 2 (DESIGN.md §12): a K=1 decode program,
        # built lazily on the first degradation — small chunks bound the
        # wasted-work exposure when the pool is starving
        self._decode_small = None
        self._decode_logits = None       # decode + logits, built on demand
        # self-speculative decode (DESIGN.md §11): K draft/verify windows of
        # W drafted tokens per dispatch; one program alongside decode_many —
        # the engine picks per block by passing (or not) a draft tree
        self._spec_jit = None
        W = getattr(ecfg, "speculate_k", 0)
        if W > 0:
            self._spec_jit = jax.jit(partial(
                lm.speculate_many, cfg, pctx=pctx, kvcfg=kvcfg, kcfg=kncfg,
                K=K, W=W, max_len=ML, detect_faults=self.detect_faults,
                eos_token=ecfg.eos_token), **out_kw)
        # acceptance telemetry (host math over the per-chunk token block)
        self.spec_windows = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._prefill_jit = jax.jit(partial(lm.prefill, cfg, pctx=pctx,
                                            collect_stats=True,
                                            full_logits=True, kvcfg=kvcfg),
                                    static_argnames=("max_len",
                                                     "compact_state"))

    def place_params(self, params):
        """Device placement for a parameter tree (fp at engine init, or a
        freshly quantized tree): mesh-sharded per ``parallel/rules.py`` when
        a mesh is active, otherwise untouched (jax default placement).  Lives
        on the runner because device allocation belongs to the runner
        (tracecheck TC402/TC405)."""
        if self.pctx is None or self.pctx.mesh is None:
            return params
        from repro.parallel.rules import shard_params
        return shard_params(params, self.pctx)

    def _repin(self):
        """Pin the slot lanes (and, after admission writes, the decode state)
        back to their canonical shardings.  Explicit ``device_put`` — legal
        under ``jax.transfer_guard("disallow")`` and a no-op when the layout
        already matches — so eager admission scatters can never drift the
        decode jit's input shardings into a recompile ping-pong."""
        if self._state_shardings is None:
            return
        self.state = jax.tree.map(jax.device_put, self.state,
                                  self._state_shardings)
        self.pos = jax.device_put(self.pos, self._rep)
        self.cur_tok = jax.device_put(self.cur_tok, self._rep)
        self.done = jax.device_put(self.done, self._rep)
        self.remaining = jax.device_put(self.remaining, self._rep)
        self.key = jax.device_put(self.key, self._rep)
        if self._poison is not None:
            self._poison = jax.device_put(self._poison, self._rep)

    @property
    def compiled_programs(self) -> int:
        """Programs resident in this runner's jit caches: the fused decode,
        the batched prefill (one entry per admission bucket shape), and the
        module-level prefix gather.  The engine's ``compiled_programs``
        facade adds the requant plan; benchmarks gate on the steady-state
        delta being zero."""
        n = (self._decode_jit._cache_size()
             + self._prefill_jit._cache_size()
             + _gather_prefix._cache_size()
             + _gather_dense_prefix._cache_size())
        if self._spec_jit is not None:
            n += self._spec_jit._cache_size()
        if self._decode_small is not None:
            n += self._decode_small._cache_size()
        return n

    def set_poison(self, slots):
        """Arm the decode-logits fault-injection site: lanes in ``slots``
        get NaN logits on every step of the next decode block
        (``lm.decode_many``'s ``poison`` input — DESIGN.md §12).  Only
        callable with guards on (the fault-detecting decode program); the
        mask crosses via one explicit ``device_put``, so injection runs
        stay transfer-guard clean."""
        if self._poison is None:
            raise RuntimeError("fault injection needs EngineConfig.guards")
        mask_h = np.zeros((self.ecfg.max_slots,), bool)
        mask_h[list(slots)] = True
        self._poison = jax.device_put(mask_h) if self._rep is None \
            else jax.device_put(mask_h, self._rep)

    # -------------------------------------------------------------- admission

    def _assemble(self, reqs, bucket: int, prefix_len: int):
        """Host-side token assembly: one transfer, tail tokens only."""
        toks_h = np.zeros((len(reqs), bucket), np.int32)
        for i, req in enumerate(reqs):
            tail = req.prompt[prefix_len:]
            toks_h[i, :len(tail)] = tail
        return jnp.asarray(toks_h)

    def admit_group(self, params, group, frames=None):
        """One bucketed prefill dispatch for ``len(group.slots)`` prompts.

        Right-pads every prompt to ``group.bucket`` (causal masking keeps the
        real tokens clean; pad positions beyond a prompt's end are never
        attended at decode — decode overwrites them), runs ONE batched
        prefill with the stats tap on, samples each row's first token, and
        writes each row's cache into its slot.

        Paged groups share a ``prefix_len``: the batch holds only the prompt
        *tails* (the cached prefix is gathered from the pool and attended at
        offset ``prefix_len``), and the compact prefill k/v is scattered
        into each slot's physical blocks.

        Returns ``(first_tokens (n,), finished (n,), stats)`` — the first two
        as host arrays (one sync for the whole group); ``finished[i]`` marks
        a request already over at admission (budget of 1, EOS on the first
        token, or a prompt that fills the cache exactly).

        Encoder-decoder requests carry per-request ``frames``; staging them
        onto the device happens *here* (not in the engine facade) — all
        array allocation belongs to the runner.
        """
        if frames is None and self.cfg.family == "encdec":
            frames = jnp.stack([
                jnp.asarray(r.frames) if r.frames.ndim == 2
                else jnp.asarray(r.frames)[0] for r in group.requests])
        if self.paged:
            return self._admit_group_paged(params, group, frames)
        batch = {"tokens": self._assemble(group.requests, group.bucket, 0)}
        if frames is not None:
            batch["frames"] = frames
        logits, sstate, stats = self._prefill_jit(params, batch,
                                                  max_len=self.ecfg.max_len)
        reqs = group.requests
        plens_h = np.asarray([len(r.prompt) for r in reqs], np.int32)
        last = jnp.take_along_axis(logits,
                                   jnp.asarray(plens_h - 1)[:, None, None],
                                   axis=1)[:, 0]
        self.state = _write_slots(self.state, sstate, group.slots)
        first_h, fin_h = self._finish_admission(group.slots, reqs, last,
                                                plens_h)
        return first_h, fin_h, stats

    def _finish_admission(self, slots, reqs, last, plens_h):
        """Shared admission epilogue: sample each row's first token, arm the
        slot lanes (pos/cur_tok/budget/done — a request can be over already:
        budget of 1, EOS first token, or a cache-filling prompt), and pull
        the one host sync for the group.

        Only ``first`` crosses the device boundary: prompt lengths and
        budgets are host-known, so the finished mask is host math — the
        old device-side ``fin`` cost an extra h2d of host-derived operands
        plus their d2h round trip for data the host already had."""
        ecfg = self.ecfg
        self.key, sk = jax.random.split(self.key)
        first = sample(last, sk, ecfg.temperature)
        idx = jnp.asarray(slots, jnp.int32)
        budget_h = np.asarray([r.remaining for r in reqs], np.int32) - 1
        self.pos = self.pos.at[idx].set(jnp.asarray(plens_h))  # decode
        self.cur_tok = self.cur_tok.at[idx].set(first[:, None])  # overwrites
        self.remaining = self.remaining.at[idx].set(jnp.asarray(budget_h))
        first_h = jax.device_get(first)  # tracecheck: ok[TC103] one designed
        #                                  sync per admission group
        fin_h = ((plens_h >= ecfg.max_len) | (budget_h <= 0)
                 | (first_h == ecfg.eos_token))
        self.done = self.done.at[idx].set(jnp.asarray(fin_h))
        self.host_syncs += 1
        self._repin()                    # admission writes → canonical layout
        return first_h, fin_h

    def _admit_group_paged(self, params, group, frames=None):
        ecfg, kvcfg = self.ecfg, self.kvcfg
        bs = kvcfg.block_size
        slots, reqs = group.slots, group.requests
        n, bucket, pfx = len(reqs), group.bucket, group.prefix_len
        batch = {"tokens": self._assemble(reqs, bucket, pfx)}
        if frames is not None:
            batch["frames"] = frames
        prefix_kv = None
        if pfx:
            nbp = pfx // bs
            ptab = jnp.asarray([[r.blocks[j] for j in range(nbp)]
                                for r in reqs], jnp.int32)
            prefix_kv = _gather_prefix(self.state["stack"], ptab, kvcfg)
        logits, sstate, stats = self._prefill_jit(
            params, batch, max_len=ecfg.max_len, prefix_kv=prefix_kv,
            pos0=pfx)
        tlens = jnp.asarray([len(r.prompt) - pfx for r in reqs], jnp.int32)
        last = jnp.take_along_axis(logits, (tlens - 1)[:, None, None],
                                   axis=1)[:, 0]
        # scatter the compact tail k/v into each slot's physical blocks;
        # pad blocks past the prompt (and any logical block the request
        # never owns) write to the sink
        nbw = -(-bucket // bs)
        pb0 = pfx // bs
        phys = np.full((n, nbw), SINK, np.int32)
        for i, r in enumerate(reqs):
            plen = len(r.prompt)
            for j in range(nbw):
                lb = pb0 + j
                if lb * bs < plen and lb < len(r.blocks):
                    phys[i, j] = r.blocks[lb]
        self.state["stack"] = _write_paged(self.state["stack"],
                                           sstate["stack"],
                                           jnp.asarray(phys), bs)
        # per-slot block-table rows (unowned entries stay at the sink)
        nblk = ecfg.max_len // bs
        rows = np.full((n, nblk), SINK, np.int32)
        for i, r in enumerate(reqs):
            rows[i, :len(r.blocks)] = r.blocks
        idx = jnp.asarray(slots, jnp.int32)
        self.state["block_table"] = \
            self.state["block_table"].at[idx].set(jnp.asarray(rows))
        plens_h = np.asarray([len(r.prompt) for r in reqs], np.int32)
        first_h, fin_h = self._finish_admission(slots, reqs, last, plens_h)
        return first_h, fin_h, stats

    def release_slots(self, slots):
        """Deactivate slots whose requests finished / were preempted or
        cancelled: done lane on, budget zeroed, and (paged) the block-table
        row pointed at the sink so the lane's clamped writes can never land
        in blocks the allocator has handed to someone else.

        Also the *parking* primitive for mid-chunked-prefill lanes
        (DESIGN.md §13): ``pos`` is pushed to ``max_len`` so a parked
        lane's done-lane garbage writes clamp to row ``max_len - 1`` —
        a row no chunk's prefix gather ever reads (gathers stop strictly
        before the prompt's last token) and every armed lane overwrites
        before reading.  Dense slabs need this; paged lanes are already
        safe via the sink row.

        Runs mid-decode (a request can finish inside the steady-state
        loop), so the slot set crosses via one explicit ``device_put`` and
        the updates are masked ``where``s over device-resident constants —
        transfer-guard clean.  (An ``.at[idx].set`` scatter would NOT be:
        eager advanced-index normalization compares the index array against
        a host scalar, an implicit h2d the guard rejects.)"""
        mask_h = np.zeros((self.ecfg.max_slots,), bool)
        mask_h[list(slots)] = True
        mask = jax.device_put(mask_h) if self._rep is None \
            else jax.device_put(mask_h, self._rep)
        self.done = jnp.logical_or(self.done, mask)
        self.remaining = jnp.where(mask, self._zero, self.remaining)
        self.pos = jnp.where(mask, self._maxlen, self.pos)
        if self.paged:
            self.state["block_table"] = jnp.where(
                mask[:, None], self._sink, self.state["block_table"])

    # -------------------------------------------------------- chunked prefill

    def prefill_chunk(self, params, plan):
        """One chunked-prefill dispatch (DESIGN.md §13): ingest prompt rows
        ``[start, start + length)`` of one request into its parked slot.

        The chunk is padded to the fixed ``prefill_chunk`` width (shape
        stability: one prefill program per distinct prefix length, not per
        tail length) and attends to the already-resident rows as tail-
        prefill context — gathered from the slot's physical blocks (paged)
        or its slab rows (dense), exactly the prefix-cache mechanics of
        DESIGN.md §8 with ``pos0 = start``.  Pad columns are causally
        masked during the chunk and land past the prompt point (sink
        blocks / overwritten-before-read slab rows), so they never
        contaminate later reads.

        Non-final chunks return ``(None, None, stats)`` — the lane stays
        parked.  The final chunk runs the shared admission epilogue:
        samples the first token from the last *real* row's logits, installs
        the (paged) block-table row, arms the lane, and returns
        ``(first (1,), finished (1,), stats)`` host arrays."""
        ecfg, kvcfg = self.ecfg, self.kvcfg
        req, slot = plan.req, plan.slot
        C = ecfg.prefill_chunk
        start, n = plan.start, plan.length
        toks_h = np.zeros((1, C), np.int32)
        toks_h[0, :n] = req.prompt[start:start + n]
        batch = {"tokens": jnp.asarray(toks_h)}
        prefix_kv = None
        if start:
            if self.paged:
                nbp = start // kvcfg.block_size
                ptab = jnp.asarray([req.blocks[:nbp]], jnp.int32)
                prefix_kv = _gather_prefix(self.state["stack"], ptab, kvcfg)
            else:
                prefix_kv = _gather_dense_prefix(
                    self.state["stack"], jnp.asarray([slot], jnp.int32),
                    pfx=start, kvcfg=kvcfg)
        logits, sstate, stats = self._prefill_jit(
            params, batch, max_len=ecfg.max_len, prefix_kv=prefix_kv,
            pos0=start, compact_state=True)
        if self.paged:
            bs = kvcfg.block_size
            nbw = C // bs                    # C % bs == 0 (engine-validated)
            pb0 = start // bs
            end = start + n
            phys = np.full((1, nbw), SINK, np.int32)
            for j in range(nbw):
                lb = pb0 + j
                if lb * bs < end and lb < len(req.blocks):
                    phys[0, j] = req.blocks[lb]
            self.state["stack"] = _write_paged(self.state["stack"],
                                               sstate["stack"],
                                               jnp.asarray(phys), bs)
        else:
            self.state["stack"] = _write_rows(self.state["stack"],
                                              sstate["stack"], slot, start,
                                              ecfg.max_len)
        if not plan.final:
            self._repin()                   # chunk writes → canonical layout
            return None, None, stats
        last = logits[:, n - 1]             # last real row's logits
        if self.paged:
            nblk = ecfg.max_len // kvcfg.block_size
            rows = np.full((1, nblk), SINK, np.int32)
            rows[0, :len(req.blocks)] = req.blocks
            idx = jnp.asarray([slot], jnp.int32)
            self.state["block_table"] = \
                self.state["block_table"].at[idx].set(jnp.asarray(rows))
        plens_h = np.asarray([len(req.prompt)], np.int32)
        first_h, fin_h = self._finish_admission([slot], [req], last, plens_h)
        return first_h, fin_h, stats

    # ----------------------------------------------------------------- decode

    def _decode_program(self, K: int, return_logits: bool = False):
        """The jitted ``lm.decode_many`` of ``K`` steps, with this runner's
        model, mesh, KV/kernel configs, fault detection and output
        shardings (the logits, when returned, replicated)."""
        ecfg, out_kw = self.ecfg, self._out_kw
        if return_logits and out_kw:
            ys, carry = out_kw["out_shardings"]
            out_kw = {"out_shardings": (ys + (self._rep,), carry)}
        return jax.jit(partial(
            lm.decode_many, self.cfg, pctx=self.pctx, kvcfg=self.kvcfg,
            kcfg=self.kncfg, K=K, max_len=ecfg.max_len,
            detect_faults=self.detect_faults, temperature=ecfg.temperature,
            eos_token=ecfg.eos_token, return_logits=return_logits), **out_kw)

    def _small_decode_jit(self):
        """Lazy K=1 decode program for degradation-ladder rung 2 — one
        compile at the first degradation, cached (and counted) afterwards,
        so an oscillating ladder never grows the jit caches."""
        if self._decode_small is None:
            self._decode_small = self._decode_program(1)
        return self._decode_small

    def _decode_args(self, params):
        args = (params, self.state, self.cur_tok, self.pos, self.done,
                self.remaining, self.key)
        return args + (self._poison,) if self.detect_faults else args

    def first_decode_logits(self, params):
        """f32 logits (B, V) of the next decode step over every slot, from
        the decode program :meth:`decode_block` runs plus a logits output;
        the slots' state is left as it was (a check reads the served
        numbers without serving a token)."""
        if self._decode_logits is None:
            self._decode_logits = self._decode_program(
                max(1, self.ecfg.decode_chunk), return_logits=True)
        ys, _ = self._decode_logits(*self._decode_args(params))
        return ys[-1][0]

    def prefill_logits(self, params, prompts):
        """f32 last-token logits (n, V) of ``prompts`` from the admission
        prefill program (one batch, right-padded to the longest prompt);
        nothing is written to the slots."""
        plens = np.asarray([len(p) for p in prompts], np.int32)
        toks = np.zeros((len(prompts), int(plens.max())), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        logits, _, _ = self._prefill_jit(params, {"tokens": jnp.asarray(toks)},
                                         max_len=self.ecfg.max_len)
        return jnp.take_along_axis(
            logits, jnp.asarray(plens - 1)[:, None, None], axis=1)[:, 0]

    def decode_program_text(self, params) -> str:
        """Compiled text of the decode program :meth:`decode_block` runs on
        ``params`` — what a check reads to see which Pallas kernels it holds
        (``tpu_custom_call`` ops on a TPU)."""
        return self._decode_jit.lower(
            *self._decode_args(params)).compile().as_text()

    def decode_block(self, params, draft_params=None, small_chunk=False):
        """Run one fused decode dispatch over every slot.

        Default: ``decode_chunk`` scanned decode steps (``lm.decode_many``).
        With ``draft_params`` (and ``EngineConfig.speculate_k`` > 0): the
        self-speculative program instead — ``decode_chunk`` draft/verify
        windows of ``speculate_k`` drafted tokens each (DESIGN.md §11), so
        the block widens to ``K·(speculate_k+1)`` candidate columns with the
        per-window acceptance length folded into ``valid``.
        ``small_chunk`` (degradation-ladder rung 2, DESIGN.md §12) swaps in
        the K=1 program; the engine only sets it after it has already
        dropped speculation (rung 1), so the two flags never combine.

        Returns host copies ``(tokens (B, cols), valid (B, cols), done (B,),
        fault (B,) | None)`` — one blocking transfer for the whole block
        either way; ``fault`` is None with guards off and marks lanes whose
        logits went non-finite otherwise (the lane emitted nothing from the
        faulting step on — the scheduler fails just that request).
        """
        spec = draft_params is not None and self._spec_jit is not None \
            and not small_chunk
        if spec:
            args = (draft_params, params, self.state, self.cur_tok, self.pos,
                    self.done, self.remaining, self.key)
            fn = self._spec_jit
        else:
            fn = self._small_decode_jit() if small_chunk else self._decode_jit
            args = (params, self.state, self.cur_tok, self.pos, self.done,
                    self.remaining, self.key)
        if self.detect_faults:
            (toks, valid, fault), carry = fn(*args, self._poison)
        else:
            (toks, valid), carry = fn(*args)
            fault = None
        (self.state, self.cur_tok, self.pos, self.done, self.remaining,
         self.key) = carry
        self.host_syncs += 1
        fetch = ((toks, valid, self.done) if fault is None
                 else (toks, valid, self.done, fault))
        out = jax.device_get(fetch)              # the ONE designed sync/chunk
        if fault is None:
            out = out + (None,)
        if spec:
            W = self.ecfg.speculate_k
            v = np.asarray(out[1]).reshape(out[1].shape[0], -1, W + 1)
            live = v[:, :, 0]                     # a live window always emits
            emitted = v.sum(axis=2)
            self.spec_windows += int(live.sum())
            self.spec_drafted += int(live.sum()) * W
            self.spec_accepted += int(np.maximum(emitted - 1, 0).sum())
        return out


@partial(jax.jit, static_argnames=("kvcfg",))
def _gather_prefix(stack_state, ptab, kvcfg):
    """Materialize the shared-prefix k/v for a tail prefill: per run, gather
    ``ptab``'s (n, nbp) physical blocks from each layer's pool and (for
    quantized layouts) dequantize to f32 — the same values (and dtype) the
    tail's quantize→dequantize attention read uses, so warm and cold
    prefills see one consistent context.  (k, v) arrays (R, n, Hkv, P, ·),
    post-rope, ready to ride the layer scan as xs."""
    from repro.core.kvquant import dequantize_kv

    out = []
    for run in stack_state:
        st = run["u0"]
        if "k" in st:
            kv = (_gather_pool(st["k"], ptab), _gather_pool(st["v"], ptab))
        else:
            kv = tuple(
                dequantize_kv(_gather_pool(st[nm + "_q"], ptab),
                              _gather_pool(st[nm + "_s"], ptab),
                              jnp.float32, bits=kvcfg.bits,
                              group_size=kvcfg.group_size)
                for nm in ("k", "v"))
        out.append(kv)
    return out


@partial(jax.jit, static_argnames=("pfx", "kvcfg"))
def _gather_dense_prefix(stack_state, slot, pfx, kvcfg):
    """Materialize one slot's first ``pfx`` dense-slab rows as tail-prefill
    context — the dense twin of :func:`_gather_prefix` for chunked prefill
    (DESIGN.md §13): chunk N attends the rows chunks < N wrote.  Quantized
    layouts dequantize to f32, matching the QDQ values the chunk's own
    attention read uses, so every chunk sees one consistent context.
    ``slot``: (1,) int32.  Returns per-run (k, v) arrays (R, 1, Hkv, pfx, ·),
    post-rope, ready to ride the layer scan as xs."""
    from repro.core.kvquant import dequantize_kv

    out = []
    for run in stack_state:
        st = run["u0"]
        if "k" in st:
            kv = (st["k"][:, slot, :, :pfx], st["v"][:, slot, :, :pfx])
        else:
            kv = tuple(
                dequantize_kv(st[nm + "_q"][:, slot, :, :pfx],
                              st[nm + "_s"][:, slot, :, :pfx],
                              jnp.float32, bits=kvcfg.bits,
                              group_size=kvcfg.group_size)
                for nm in ("k", "v"))
        out.append(kv)
    return out
