"""Top-level language model — embed → stack(s) → norm → vocab head.

Uniform API across all 10 assigned families:

    init_params(cfg, key)                       → params pytree
    forward(cfg, params, batch, ...)            → (logits, stats, states)
    loss_fn(cfg, params, batch, ...)            → (loss, aux)
    init_decode_state(cfg, batch, max_len)      → DecodeState
    prefill(cfg, params, batch, max_len, ...)   → (last_logits, state, stats)
    decode_step(cfg, params, state, token, pos) → (logits, state)
    decode_many(cfg, params, state, token, pos, done, remaining, key, K=...)
                                                → ((tokens, valid), carry)

``batch`` is a dict: {'tokens': (B,S) int32} and, for encdec, also
{'frames': (B, n_frames, d_model)} — the spec'd stub modality frontend.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import stack as S
from .common import linear, norm, init_norm, sample_logits, sinusoidal_pos
from .config import ModelConfig

P = jax.sharding.PartitionSpec


def _wsc(x, spec, pctx):
    if pctx is None or pctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(pctx.mesh, spec))


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    p: dict = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, D), jnp.float32)
                  * D ** -0.5).astype(jnp.bfloat16),
        "stack": S.init_stack(ks[1], cfg, S.stack_spec(cfg)),
        "final_norm": init_norm(D, "rms" if cfg.norm == "rms" else "layer"),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[2], (cfg.vocab, D), jnp.float32)
                        * D ** -0.5).astype(jnp.bfloat16)
    if cfg.pos == "learned":
        p["pos_embed"] = (jax.random.normal(ks[3], (cfg.max_seq, D), jnp.float32)
                          * 0.02).astype(jnp.bfloat16)
    if cfg.family == "encdec":
        p["enc_stack"] = S.init_stack(ks[4], cfg, S.enc_spec(cfg))
        p["enc_norm"] = init_norm(D, "rms" if cfg.norm == "rms" else "layer")
    return p


def _embed(cfg, params, tokens, pctx, pos0: int = 0):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.pos == "learned":
        S_ = tokens.shape[1]
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos0, S_, 0)[None]
    dp = None if pctx is None else pctx.data_axes
    return _wsc(x, P(dp, None, None), pctx)


def _head(cfg, params, x, pctx, kcfg=None):
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = linear(x, w, kcfg=kcfg, pctx=pctx, tp="row").astype(jnp.float32)
    dp = None if pctx is None else pctx.data_axes
    mp = None if pctx is None else pctx.model_axis
    return _wsc(logits, P(dp, None, mp), pctx)


def _encode(cfg, params, frames, pctx, stats_on=False):
    x = frames.astype(jnp.bfloat16) + sinusoidal_pos(frames.shape[1], cfg.d_model)[None]
    x, st, _ = S.apply_stack_seq(cfg, params["enc_stack"], S.enc_spec(cfg), x,
                                 stats_on=stats_on, pctx=pctx)
    return norm(x, params["enc_norm"]), st


def forward(cfg: ModelConfig, params, batch, *, collect_stats=False, pctx=None,
            want_state=False, max_len=0, remat=False, kcfg=None):
    """Full-sequence forward. Returns (logits, stats, states).

    stats: {'stack': [per-run dict], 'enc_stack': [...]} of Σx² leaves
    (leading run-repeat dim), path-aligned with params for the TTQ join.
    """
    tokens = batch["tokens"]
    enc_out = None
    stats: dict = {}
    if cfg.family == "encdec":
        enc_out, enc_stats = _encode(cfg, params, batch["frames"], pctx,
                                     stats_on=collect_stats)
        if collect_stats:
            stats["enc_stack"] = enc_stats
    x = _embed(cfg, params, tokens, pctx)
    x, run_stats, states = S.apply_stack_seq(
        cfg, params["stack"], S.stack_spec(cfg), x, stats_on=collect_stats,
        pctx=pctx, enc_out=enc_out, want_state=want_state, max_len=max_len,
        remat=remat, kcfg=kcfg)
    if collect_stats:
        stats["stack"] = run_stats
    x = norm(x, params["final_norm"])
    logits = _head(cfg, params, x, pctx, kcfg)
    return logits, (stats if collect_stats else None), states


def loss_fn(cfg: ModelConfig, params, batch, *, pctx=None, remat=False):
    """Next-token cross-entropy (vocab-sharded logsumexp — no full gather)."""
    logits, _, _ = forward(cfg, params, batch, pctx=pctx, remat=remat)
    targets = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    if mask.shape[1] == batch["tokens"].shape[1]:
        mask = mask[:, 1:]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    return loss, {"loss": loss, "tokens": denom}


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, kvcfg=None,
                      num_blocks: int = 0):
    """``kvcfg`` (:class:`repro.core.KVCacheConfig`) selects the attention
    cache layout: None/bf16 → the seed {'k','v'} bf16 slots; int8/int4 →
    quantized codes + per-(head, token) scales (DESIGN.md §"KV-cache layout").

    With ``kvcfg.paged`` the per-layer caches become shared block pools of
    ``num_blocks`` blocks and the state carries a per-slot ``block_table``
    (B, max_len/block_size) int32 — rows map logical to physical blocks; 0
    is the sink block for unallocated entries and done-lane writes
    (DESIGN.md §8)."""
    paged = kvcfg is not None and kvcfg.paged
    if paged:
        if max_len % kvcfg.block_size:
            raise ValueError(f"max_len={max_len} must divide by "
                             f"block_size={kvcfg.block_size}")
        if num_blocks < 2:
            raise ValueError("paged cache needs num_blocks >= 2 "
                             "(block 0 is the reserved sink)")
    st: dict = {"stack": S.init_stack_state(cfg, S.stack_spec(cfg), batch,
                                            max_len, kvcfg, num_blocks)}
    if paged:
        st["block_table"] = jnp.zeros((batch, max_len // kvcfg.block_size),
                                      jnp.int32)
    if cfg.family == "encdec":
        st["enc_out"] = jnp.zeros((batch, cfg.encdec.n_frames, cfg.d_model),
                                  jnp.bfloat16)
    return st


def prefill(cfg: ModelConfig, params, batch, max_len: int, *,
            collect_stats=True, pctx=None, full_logits=False, kvcfg=None,
            prefix_kv=None, pos0: int = 0, compact_state: bool = False):
    """Run the prompt, build decode state + TTQ activation statistics.

    ``prefix_kv``/``pos0`` (paged prefix-cache hits, DESIGN.md §8): the
    tokens are the prompt *tail*, attending to the cached prefix k/v (a
    per-run list of (k, v) with leading layer dim, post-rope) at absolute
    offset ``pos0``.  The returned paged state is compact — this call's
    rows only; the cached prefix stays where it is.  ``compact_state``
    forces the compact layout for dense caches too (chunked prefill,
    DESIGN.md §13 — the runner owns the row writes)."""
    tokens = batch["tokens"]
    enc_out = None
    stats: dict = {}
    if cfg.family == "encdec":
        enc_out, enc_stats = _encode(cfg, params, batch["frames"], pctx,
                                     stats_on=collect_stats)
        if collect_stats:
            stats["enc_stack"] = enc_stats
    x = _embed(cfg, params, tokens, pctx, pos0=pos0)
    x, run_stats, states = S.apply_stack_seq(
        cfg, params["stack"], S.stack_spec(cfg), x, stats_on=collect_stats,
        pctx=pctx, enc_out=enc_out, want_state=True, max_len=max_len,
        kvcfg=kvcfg, pos0=pos0, prefix_kv=prefix_kv,
        compact_state=compact_state)
    if collect_stats:
        stats["stack"] = run_stats
    x = norm(x, params["final_norm"])
    if full_logits:
        logits = _head(cfg, params, x, pctx)
    else:
        logits = _head(cfg, params, x[:, -1:], pctx)[:, 0]
    state: dict = {"stack": states}
    if enc_out is not None:
        state["enc_out"] = enc_out
    return logits, state, (stats if collect_stats else None)


def decode_step(cfg: ModelConfig, params, state, token, pos, *, pctx=None,
                kvcfg=None, kcfg=None):
    """token: (B,1) int32; pos: (B,) int32 per-slot positions (scalar ok).

    ``kvcfg`` must match the layout ``state`` was initialized with (it is a
    static jit arg — the engine threads the same config everywhere)."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (token.shape[0],))
    x = jnp.take(params["embed"], token, axis=0)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[:, None]
    dp = None if pctx is None else pctx.data_axes
    x = _wsc(x, P(dp, None, None), pctx)
    x, new_states = S.apply_stack_decode(cfg, params["stack"], S.stack_spec(cfg),
                                         state["stack"], x, pos, pctx=pctx,
                                         kvcfg=kvcfg, kcfg=kcfg,
                                         block_table=state.get("block_table"))
    x = norm(x, params["final_norm"])
    logits = _head(cfg, params, x, pctx, kcfg)
    new_state = dict(state)
    new_state["stack"] = new_states
    return logits[:, 0], new_state


def decode_many(cfg: ModelConfig, params, state, token, pos, done, remaining,
                key, poison=None, *, K: int, max_len: int,
                temperature: float = 0.0, eos_token: int = -1,
                detect_faults: bool = False, pctx=None, kvcfg=None,
                kcfg=None, return_logits: bool = False):
    """Fused multi-token decode: ``lax.scan`` over ``K`` decode steps keeping
    sampling, EOS detection, per-slot done-masking, budget accounting, and
    position advance entirely on device — one host transfer per K tokens
    instead of one per token per slot.

    Inputs (all device arrays; B = slot count):
      token     (B, 1) int32  current token per slot
      pos       (B,)   int32  cache write position per slot
      done      (B,)   bool   True = inactive/finished lane (computes but
                              emits nothing; pos/token held)
      remaining (B,)   int32  generation budget left per slot
      key       PRNG key — split once per step, mirroring the host loop

    A live slot finishes when it emits ``eos_token``, exhausts ``remaining``,
    or its cache fills (``pos`` reaching ``max_len``): the request *ends* at
    capacity rather than clipping ``pos`` and silently overwriting the last
    KV row.  Done lanes keep stepping with ``pos`` clamped in-bounds; their
    garbage writes land in slots the next admission fully overwrites.

    Returns ``((tokens (B, K) int32, valid (B, K) bool), (state, token, pos,
    done, remaining, key))``.  ``valid[b, k]`` marks tokens actually emitted
    by a live slot; with greedy sampling those tokens are identical to ``K``
    repeated :func:`decode_step` calls.

    **Fault isolation (DESIGN.md §12):** with ``detect_faults=True`` the
    per-step logits are checked for finiteness on device; a lane whose
    logits go non-finite emits *nothing* from that step on (its done flag
    trips, position/token hold) and the output triple gains a per-slot
    ``fault (B,) bool`` — ``((tokens, valid, fault), carry)`` — so the
    scheduler can fail just that lane.  ``poison`` ((B,) bool or None) is
    the deterministic injection site: flagged lanes get their logits forced
    to NaN post-projection, exercising the exact detection path a real
    numerical fault would take.  Both default off, preserving the original
    signature and program for every existing caller.

    ``return_logits`` appends every step's f32 logits (K, B, V) to the
    output tuple — the same program with one more output, for checks that
    compare the served numbers (``DeviceRunner.first_decode_logits``).
    """
    def step_fn(carry, _):
        st, tok, p, dn, rem, k = carry
        p_in = jnp.minimum(p, max_len - 1)      # done lanes: in-bounds writes
        logits, st = decode_step(cfg, params, st, tok, p_in, pctx=pctx,
                                 kvcfg=kvcfg, kcfg=kcfg)
        if poison is not None:
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)
        k, sk = jax.random.split(k)
        live = ~dn
        if detect_faults:
            flt = live & ~jnp.isfinite(logits).all(axis=-1)
            live = live & ~flt                  # faulted lane: emit nothing,
            dn = dn | flt                       # hold token/pos, trip done
        nxt = sample_logits(logits, sk, temperature)
        nxt = jnp.where(live, nxt, tok[:, 0])
        rem = rem - live.astype(jnp.int32)
        p = p + live.astype(jnp.int32)
        stop = (nxt == eos_token) | (p >= max_len) | (rem <= 0)
        dn = dn | (live & stop)
        ys = (nxt, live, flt) if detect_faults else (nxt, live)
        if return_logits:
            ys += (logits.astype(jnp.float32),)
        return (st, nxt[:, None], p, dn, rem, k), ys

    carry = (state, token, pos, done, remaining, key)
    carry, ys = jax.lax.scan(step_fn, carry, None, length=K)
    out = (ys[0].T, ys[1].T)
    if detect_faults:
        out += (ys[2].any(axis=0),)
    if return_logits:
        out += (ys[-1],)
    return out, carry


def verify_window(cfg: ModelConfig, params, state, tokens, pos, *, pctx=None,
                  kvcfg=None, kcfg=None):
    """Score a drafted window in one batched dispatch (DESIGN.md §11).

    tokens: (B,S) int32 — per slot, the current token followed by S-1 drafted
    tokens, fed at absolute positions ``pos[b]..pos[b]+S-1``.  Writes the
    window's KV rows with THIS tree's k/v (overwriting whatever the draft
    pass stored there), then reads the updated cache, so the returned logits
    (B,S,V) match S sequential :func:`decode_step` calls bit-for-bit.
    """
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.pos == "learned":
        idx = pos[:, None] + jnp.arange(tokens.shape[1])
        x = x + jnp.take(params["pos_embed"], idx, axis=0)
    dp = None if pctx is None else pctx.data_axes
    x = _wsc(x, P(dp, None, None), pctx)
    x, new_states = S.apply_stack_verify(cfg, params["stack"], S.stack_spec(cfg),
                                         state["stack"], x, pos, pctx=pctx,
                                         kvcfg=kvcfg, kcfg=kcfg,
                                         block_table=state.get("block_table"))
    x = norm(x, params["final_norm"])
    logits = _head(cfg, params, x, pctx, kcfg)
    new_state = dict(state)
    new_state["stack"] = new_states
    return logits, new_state


def speculate_many(cfg: ModelConfig, draft_params, params, state, token, pos,
                   done, remaining, key, poison=None, *, K: int, W: int,
                   max_len: int, eos_token: int = -1,
                   detect_faults: bool = False, pctx=None, kvcfg=None,
                   kcfg=None):
    """Self-speculative fused decode: ``K`` draft/verify windows per dispatch
    (DESIGN.md §11).  Greedy only — the engine auto-disables speculation when
    sampling temperature > 0.

    Each window drafts ``W`` tokens with ``draft_params`` (a ``lax.scan`` of
    cheap :func:`decode_step` calls), then scores the whole window — current
    token plus the W drafts — with ``params`` in ONE batched
    :func:`verify_window` dispatch.  On-device greedy acceptance keeps the
    longest agreeing prefix plus the verifier's next token (the standard
    bonus/correction), so every window emits between 1 and W+1 tokens per
    live slot.  KV rollback is positional: the verify pass rewrites the
    window's rows at verify quality, and rejected rows sit at or beyond the
    new frontier where the next window's write-then-read overwrites them
    before any valid query reads them — block tables never move (blocks are
    pre-reserved for ``max_new``), dense slabs just rewind positions.

    Same carry protocol as :func:`decode_many`; returns ``((tokens
    (B, K·(W+1)) int32, valid (B, K·(W+1)) bool), carry)`` — the acceptance
    length per window is recoverable from ``valid``, folding it into the
    existing one-host-transfer-per-chunk protocol.

    ``poison`` / ``detect_faults`` mirror :func:`decode_many` (DESIGN.md
    §12): the *verify* logits are the checked (and poisoned) site — the
    verify tree decides every emitted token, so a non-finite draft can only
    lower acceptance while a non-finite verify window trips the lane's
    fault flag and emits nothing.  With ``detect_faults`` the output triple
    gains the per-slot ``fault (B,) bool``.
    """
    B = token.shape[0]

    def window_fn(carry, _):
        st, tok, p, dn, rem, k = carry

        def draft_step(c, _):
            st_d, tk, pp = c
            p_in = jnp.minimum(pp, max_len - 1)
            logits, st_d = decode_step(cfg, draft_params, st_d, tk, p_in,
                                       pctx=pctx, kvcfg=kvcfg, kcfg=kcfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (st_d, nxt[:, None], pp + 1), nxt

        (st, _, _), drafts = jax.lax.scan(draft_step, (st, tok, p), None,
                                          length=W)
        drafts = drafts.T                                   # (B, W)
        win = jnp.concatenate([tok, drafts], axis=1)        # (B, W+1)
        logits, st = verify_window(cfg, params, st, win, p, pctx=pctx,
                                   kvcfg=kvcfg, kcfg=kcfg)
        if poison is not None:
            logits = jnp.where(poison[:, None, None], jnp.float32(jnp.nan),
                               logits)
        flt = jnp.zeros((B,), bool)
        if detect_faults:
            flt = (~dn) & ~jnp.isfinite(logits).all(axis=(-2, -1))
            dn = dn | flt                   # faulted lane: whole window out
        v = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, W+1)
        # longest agreeing draft prefix; candidate i (0-based) is the
        # verifier's token for position p+i+1 and is emitted iff i <= a
        agree = (drafts == v[:, :W]).astype(jnp.int32)
        a = jnp.cumprod(agree, axis=1).sum(axis=1)          # (B,)

        def emit_step(c, xs):
            tk, pp, d2, rm = c
            vi, i = xs
            use = (~d2) & (i <= a)
            nxt = jnp.where(use, vi, tk[:, 0])
            rm = rm - use.astype(jnp.int32)
            pp = pp + use.astype(jnp.int32)
            stop = (nxt == eos_token) | (pp >= max_len) | (rm <= 0)
            d2 = d2 | (use & stop)
            return (nxt[:, None], pp, d2, rm), (nxt, use)

        (tok, p, dn, rem), (toks_w, valid_w) = jax.lax.scan(
            emit_step, (tok, p, dn, rem), (v.T, jnp.arange(W + 1)))
        ys = (toks_w, valid_w, flt) if detect_faults else (toks_w, valid_w)
        return (st, tok, p, dn, rem, k), ys

    carry = (state, token, pos, done, remaining, key)
    carry, ys = jax.lax.scan(window_fn, carry, None, length=K)
    toks, valid = ys[0], ys[1]
    # (K, W+1, B) → (B, K·(W+1)), window-major per slot
    toks = toks.transpose(2, 0, 1).reshape(B, K * (W + 1))
    valid = valid.transpose(2, 0, 1).reshape(B, K * (W + 1))
    if detect_faults:
        return (toks, valid, ys[2].any(axis=0)), carry
    return (toks, valid), carry
