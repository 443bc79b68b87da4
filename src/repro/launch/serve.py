"""Production serving launcher — TTQEngine with a synthetic request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma_7b --smoke \
        --requests 8 --bits 4 --rank 16 --kv-dtype int8

Mixed precision is declared through policy overrides (repro.quant), e.g.
``--attn-bits 4 --mlp-bits 3`` gives attention projections 4-bit and MLPs
3-bit (outlier-heavy projections tolerate fewer bits worse — keep them wide).
``--kv-dtype int8|int4`` switches the engine's KV-cache memory layout to
quantized codes + per-(head, token) scales, read by the fused Pallas
dequant-attention kernel (``--kv-no-pallas`` forces the jnp fallback).

``--decode-chunk K`` fuses K decode steps into one on-device block
(``lm.decode_many``) — one host sync per K tokens instead of one per token
(``0`` picks the bench-calibrated default per slot count);
``--recal-tokens N`` drives the requantization cadence by a token budget
instead of per-admission (DESIGN.md §"Serving architecture").

``--use-kernels`` turns on the packed-weight fast path end to end: weights
quantize to packed int codes and every decode matmul dispatches the Pallas
``ttq_gemm``; ``--requant-threshold T`` arms the delta gate — only layers
whose activation diagonal drifted ≥ T (relative L2) re-quantize, the rest
reuse their previous packed tensors.  The end-of-run summary reports the
gate's skip counts and the requantization wall time next to
``host_syncs/token``.

``--kv-paged`` switches the slot caches to the block-paged pool (DESIGN.md
§8): ``--kv-block-size`` sets the block granularity, ``--kv-pool-blocks``
the per-layer pool budget (0 = capacity-equivalent to the dense slab;
smaller budgets oversubscribe — admissions preempt running slots under
pressure instead of stalling), and ``--no-prefix-cache`` disables shared
prompt-prefix block reuse.  The summary then adds ``kv_pool_util`` (peak),
``prefix_hit_rate`` and the preemption count.

``--prefill-chunk C`` ingests long prompts in C-token chunks interleaved
with decode rounds (DESIGN.md §13) so a long arrival cannot stall running
streams for a whole monolithic prefill; ``--prefill-budget N`` bounds the
padded prefill tokens per round, ``--max-queue D`` bounds the admission
queue (``QueueFull`` past D).  The summary then adds TTFT/ITL p50/p99 and
the chunk/queue counters.

``--deadline-s T`` gives every request a T-second deadline (expired
requests fail cleanly, never stall the drain loop); ``--inject NAME``
runs a named deterministic fault recipe (``serving.faults.demo_injector``)
against the live engine and the summary reports what fired and what the
guards caught; ``--no-guards`` strips the robustness layer entirely
(DESIGN.md §12) — byte-identical to the pre-guard engine.
"""
import argparse
import time


def build_policy(args):
    """CLI flags → QuantPolicy with per-layer mixed-precision overrides."""
    from repro.quant import (KVCacheConfig, KernelConfig, NO_QUANT, override,
                             ttq_policy)

    kvcache = KVCacheConfig(dtype=args.kv_dtype,
                            group_size=args.kv_group_size,
                            use_pallas=not args.kv_no_pallas)
    kernel = KernelConfig(use_pallas=args.use_kernels)
    if args.no_quant:
        return NO_QUANT.with_(kvcache=kvcache, kernel=kernel)
    policy = ttq_policy(bits=args.bits, group_size=args.group_size,
                        rank=args.rank, kvcache=kvcache, kernel=kernel,
                        packed=args.use_kernels or args.packed)
    ovr = []
    if args.attn_bits:
        ovr.append(override("*.mix.*", bits=args.attn_bits))
    if args.mlp_bits:
        ovr.append(override("*.mlp.*", bits=args.mlp_bits))
    return policy.with_overrides(*ovr) if ovr else policy


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI (``chip_smoke.py`` parses its engine flags here too)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=32)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--decode-chunk", type=int, default=0,
                    help="K fused on-device decode steps per host sync "
                         "(lm.decode_many; 1 = per-token round trips; "
                         "0 = auto per slot count, bench_engine crossover)")
    ap.add_argument("--recal-tokens", type=int, default=0,
                    help="requantize every N processed tokens instead of "
                         "every --recal-every admissions (0 = off)")
    ap.add_argument("--recal-every", type=int, default=1,
                    help="requantize after every N admissions")
    ap.add_argument("--use-kernels", action="store_true",
                    help="packed int weights + Pallas ttq_gemm on every "
                         "decode matmul (the paper's fast path end to end)")
    ap.add_argument("--packed", action="store_true",
                    help="pack weight codes (implied by --use-kernels)")
    ap.add_argument("--requant-threshold", type=float, default=-1.0,
                    help="delta gate: requantize only layers whose "
                         "activation diagonal drifted >= T in relative L2 "
                         "(<0 = always requantize everything)")
    ap.add_argument("--double-buffer", action="store_true",
                    help="readiness-gated requant swap: decode keeps the "
                         "previous tree until the new one is device-ready "
                         "(tokens become device-timing-dependent)")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--attn-bits", type=int, default=0,
                    help="override bits for attention projections (0 = base)")
    ap.add_argument("--mlp-bits", type=int, default=0,
                    help="override bits for MLP projections (0 = base)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8", "int4"),
                    help="KV-cache storage dtype (int4 is packed 8/int32)")
    ap.add_argument("--kv-group-size", type=int, default=0,
                    help="KV scale group along head dim (0 = per head-token)")
    ap.add_argument("--kv-no-pallas", action="store_true",
                    help="jnp fallback for the dequant-attention read")
    ap.add_argument("--kv-paged", action="store_true",
                    help="block-paged KV pool + per-slot block tables with "
                         "prefix caching and preemption (plain-attention "
                         "families)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per paged pool block")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="per-layer pool blocks incl. the sink (0 = "
                         "capacity-equivalent to the dense slab)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared prompt-prefix block reuse")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="self-speculative decoding (DESIGN.md §11): draft "
                         "W tokens per window with the int4 draft tree, "
                         "verify in one batched dispatch (0 = off; greedy "
                         "only — ignored when temperature > 0)")
    ap.add_argument("--draft-bits", type=int, default=0,
                    help="explicit draft-tree precision (rank-0, g32) for "
                         "--speculate-k; 0 = the policy's int4 draft_variant."
                         "  With --no-quant this is draft-only quantization:"
                         " the quantized draft speculates for the fp model")
    ap.add_argument("--mesh", type=int, default=1,
                    help="model-parallel mesh size (tensor/expert parallel "
                         "serving, DESIGN.md §10); 1 = single device")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline in seconds (DESIGN.md §12); "
                         "expired requests fail cleanly with error="
                         "'deadline exceeded' (0 = no deadline)")
    ap.add_argument("--inject", default="",
                    help="named fault-injection recipe (serving.faults."
                         "demo_injector): nan-stats, outlier-stats, "
                         "bad-requant, pool-steal, poison-lane.  Seeded and "
                         "deterministic; the summary reports what fired and "
                         "what the guards caught")
    ap.add_argument("--no-guards", action="store_true",
                    help="disable the robustness layer (calibration guards, "
                         "requant health gate, lane fault isolation, "
                         "degradation ladder) — restores the exact pre-guard "
                         "engine")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill (DESIGN.md §13): ingest prompt "
                         "tails longer than C tokens in C-sized chunks "
                         "interleaved with decode rounds, bounding the "
                         "per-round stall a long prompt inflicts on running "
                         "streams (0 = monolithic; paged pools need C to "
                         "divide --kv-block-size)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="padded prefill tokens dispatched per engine round "
                         "across all chunk-ingesting requests (0 = one "
                         "chunk per round)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue: submit() raises "
                         "QueueFull at this depth (the async TTQServer "
                         "awaits instead; 0 = unbounded)")
    return ap


def build_engine(args, cfg=None, params=None):
    """Parsed serving flags → :class:`~repro.serving.TTQEngine`.

    ``cfg`` defaults to ``--arch`` (``--smoke`` for the reduced config) and
    ``params`` to its weights from ``PRNGKey(0)``; a caller that sizes the
    model itself (``chip_smoke.py``) passes both."""
    import jax

    from repro.configs import get
    from repro.models import lm
    from repro.serving import EngineConfig, TTQEngine

    pctx = None
    if args.mesh > 1:
        from repro.launch.mesh import make_ctx, make_mesh
        pctx = make_ctx(make_mesh(1, args.mesh))
    if cfg is None:
        cfg = get(args.arch, smoke=args.smoke)
    if params is None:
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
    policy = build_policy(args)
    faults = None
    if args.inject:
        from repro.serving import demo_injector
        faults = demo_injector(args.inject)
    draft_policy = None
    if args.speculate_k > 0 and args.draft_bits > 0:
        from repro.quant import ttq_policy
        draft_policy = ttq_policy(bits=args.draft_bits, group_size=32,
                                  rank=0, kvcache=policy.kvcache,
                                  kernel=policy.kernel,
                                  packed=args.use_kernels or args.packed)
    eng = TTQEngine(cfg, params, policy,
                    EngineConfig(max_slots=args.slots, max_len=args.max_len,
                                 decode_chunk=args.decode_chunk,
                                 recalibrate_every=args.recal_every,
                                 recalibrate_tokens=args.recal_tokens,
                                 requant_threshold=args.requant_threshold,
                                 double_buffer=args.double_buffer,
                                 kv_paged=args.kv_paged or None,
                                 kv_block_size=args.kv_block_size
                                 if args.kv_paged else 0,
                                 kv_pool_blocks=args.kv_pool_blocks,
                                 prefix_cache=not args.no_prefix_cache,
                                 speculate_k=args.speculate_k,
                                 guards=not args.no_guards,
                                 deadline_s=args.deadline_s,
                                 prefill_chunk=args.prefill_chunk,
                                 prefill_budget=args.prefill_budget,
                                 max_queue=args.max_queue),
                    pctx=pctx, draft_policy=draft_policy, faults=faults)
    return eng


def main(argv=None):
    args = build_parser().parse_args(argv)

    import jax
    import numpy as np

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    eng = build_engine(args)
    cfg, policy, faults, pctx = eng.cfg, eng.policy, eng.faults, eng.pctx
    layout = (f"paged block={eng.kvcfg.block_size} "
              f"pool={eng.num_blocks} blocks/layer "
              f"prefix_cache={not args.no_prefix_cache}"
              if eng.kvcfg.paged else "dense slab")
    print(f"kv-cache: dtype={eng.kvcfg.dtype} "
          f"group_size={eng.kvcfg.group_size or 'per-head-token'} "
          f"pallas={eng.kvcfg.use_pallas} layout={layout}")
    gate = (f"delta-gate >= {args.requant_threshold}"
            if args.requant_threshold >= 0 else "always-full")
    print(f"weight kernels: pallas={eng.kncfg.use_pallas} "
          f"packed={policy.packed}, requant: {gate}")
    cadence = (f"every {args.recal_tokens} tokens" if args.recal_tokens
               else f"every {args.recal_every} admissions")
    unit = "windows" if eng.ecfg.speculate_k > 0 else "tokens"
    print(f"decode-chunk: {eng.ecfg.decode_chunk} {unit}/dispatch, "
          f"requant cadence: {cadence}")
    if eng.ecfg.speculate_k > 0:
        dp = eng.draft_policy
        dd = (f"int{dp.qcfg.bits} g{dp.qcfg.group_size}"
              if dp is not None and dp.any_enabled else "fp (no-quant)")
        print(f"speculate: W={eng.ecfg.speculate_k} drafted tokens/window, "
              f"draft tree {dd}")
    if pctx is not None:
        print(f"mesh: (1, {args.mesh}) data×model over "
              f"{jax.device_count()} device(s)")
    dl = f"{args.deadline_s:.1f}s" if args.deadline_s > 0 else "none"
    print(f"guards: {'off' if args.no_guards else 'on'} deadline={dl} "
          f"inject={args.inject or 'none'}")
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(4, min(24, args.max_len // 2)))
        prompt = list(rng.integers(1, cfg.vocab, size=plen))
        kw = {}
        if cfg.family == "encdec":
            kw["frames"] = np.asarray(rng.standard_normal(
                (cfg.encdec.n_frames, cfg.d_model)), np.float32)
        eng.submit(prompt, max_new=args.max_new, **kw)
    outs = eng.run_all()
    dt = time.time() - t0
    toks = sum(len(v) for v in outs.values())
    skipped = eng.layers_skipped
    total_layers = eng.layers_skipped + eng.layers_requantized
    print(f"arch={cfg.name} requests={len(outs)} tokens={toks} "
          f"wall={dt:.1f}s requants={eng.n_requants} "
          f"host_syncs/token={eng.host_syncs / max(toks, 1):.2f} "
          f"requant_wall={eng.requant_wall_s:.2f}s "
          f"gate_skipped_layers={skipped}/{total_layers}")
    lat = eng.latency_percentiles()
    print(f"latency: ttft p50/p99 {lat['ttft_p50'] * 1e3:.1f}/"
          f"{lat['ttft_p99'] * 1e3:.1f} ms, itl p50/p99 "
          f"{lat['itl_p50'] * 1e3:.1f}/{lat['itl_p99'] * 1e3:.1f} ms "
          f"({lat['n_streams']} streams)")
    if eng.ecfg.prefill_chunk > 0 or eng.ecfg.max_queue > 0:
        print(f"slo: prefill_chunks={eng.prefill_chunks} "
              f"queue_rejections={eng.queue_rejections} "
              f"queue_depth={eng.queue_depth}")
    if eng.ecfg.speculate_k > 0:
        print(f"speculate: windows={eng.spec_windows} "
              f"acceptance={eng.spec_acceptance_rate:.2f} "
              f"(accepted drafts / drafted tokens)")
    if eng.kvcfg.paged:
        print(f"kv-pool: util_peak={eng.kv_pool_utilization:.2f} "
              f"prefix_hit_rate={eng.prefix_hit_rate:.2f} "
              f"preemptions={eng.preemptions} "
              f"prefill_tokens={eng.prefill_tokens:.0f}")
    if not args.no_guards:
        print(f"guards: calib_rejections={eng.calib_rejections} "
              f"requant_rejections={eng.requant_rejections} "
              f"lane_faults={eng.lane_faults} "
              f"deadline_expirations={eng.deadline_expirations} "
              f"admission_failures={eng.admission_failures} "
              f"degrade_events={eng.degrade_events}")
    failed = [r for r, v in sorted(outs.items()) if v.error]
    if faults is not None:
        fired = ", ".join(f"{s}@{n}" for s, n, _ in faults.fired) or "none"
        print(f"faults fired: {fired}")
    if failed:
        print(f"  failed rids: {failed}")
    for rid, v in sorted(outs.items())[:4]:
        print(f"  rid={rid}: {v[:10]}{'…' if len(v) > 10 else ''}")
    # an injected fault is expected to fail requests; any other error is
    # the run's failure
    return 1 if failed and faults is None else 0


if __name__ == "__main__":
    raise SystemExit(main())
