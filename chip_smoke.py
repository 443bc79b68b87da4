#!/usr/bin/env python3
"""Bring-up check of the TTQ serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips

One chip: every Pallas kernel of the serving path runs once at minitron-4b's
widths against its jnp oracle (``kernels/ref.py``); then minitron-4b at its
published widths (seeded random weights) serves 8 requests through the
engine ``launch/serve.py`` builds — packed int4 weights on ``ttq_gemm``, an
int8 paged KV pool on ``ttq_attn``, online requantization on
``ttq_quantize`` at every admission — and the decode and requant programs
it compiled are shown to contain the kernels.  Four chips: the same model
on a (1, 4) tensor-parallel mesh against one chip, in one process,
comparing prefill and first-decode logits read out of each engine's own
programs, and the kernels in each engine's programs.

Every phase runs in this one process.  The last line of standard output is
``{"ok": true, "device": {...}}``; it is printed only when every check
passed.  With no TPU (``JAX_PLATFORMS=cpu`` included) or without the
repository's ``src/`` next to this file, the script exits non-zero and
prints no such line.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the checkout.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
ARCH = "minitron_4b"
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (64, 512), 32
# serving flags, as ``launch/serve.py`` takes them: TTQ int4 g32 rank 0 (the
# serve defaults), packed weights on the Pallas ttq_gemm, an int8 KV pool
# paged in 16-token blocks.  The chunk lifts the 256-token prompt-bucket cap
# so 512-token prompts are admissible.
SERVE_FLAGS = ["--arch", ARCH, "--use-kernels", "--kv-dtype", "int8",
               "--kv-paged", "--kv-block-size", "16", "--slots", "4",
               "--max-len", "1024", "--max-new", str(MAX_NEW),
               "--prefill-chunk", "256"]
PROBE_LENS = (64, 100, 160, 256)      # one admission group per length
# kernel checks: max |kernel − oracle| / max |oracle| (or code steps), with
# bf16 activations as served.  The oracles in kernels/ref.py round the same
# operands to bf16 as the kernels and accumulate at full f32 precision.
GEMM_TOL = 5e-4        # f32 out; a last-bit difference in the f32
#                        dequantization (fused multiply-add or not) flips
#                        the bf16 rounding of a few thousand weights: 1.5e-4
#                        at these widths on the CPU; no bf16 rounding of the
#                        weight at all would move 1.7e-3
ATTN_TOL = 8e-3        # both outputs are bf16: a last-bit difference before
#                        that rounding is one bf16 step, 2^-8 of an element
QSCALE_TOL = 1e-5      # scale/zero: same f32 min/max/divide
QCODE_TOL = 1          # codes: a rounding tie may land one code apart
QCODE_SHARE = 1e-3     # ...in at most this share of codes
MESH_TOL = 2e-2        # TP vs one chip: bf16 activations, f32 psum order
CUT_LAYERS = 16        # depth if all 32 layers overflow one chip's memory


class SmokeFailure(Exception):
    pass


FAILED = []        # failed checks; later phases still run and report


def check(ok, what):
    if not ok:
        FAILED.append(what)
        log(f"CHECK FAILED: {what}")


def log(msg):
    print(msg, flush=True)


# ------------------------------------------------------------------ devices

def tpu_devices():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX backend is {devs[0].platform!r}")
    return devs


class CompileClock:
    """Seconds JAX spends compiling (or fetching from the persistent cache)."""

    def __init__(self):
        import jax
        self.seconds, self.cache_hits = 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)


# ------------------------------------------------------------ kernel checks

def kernel_checks(key):
    """Each kernel once at minitron-4b widths against its jnp oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kvquant import KVCacheConfig, quantize_kv
    from repro.core.ttq import unpack_weight
    from repro.kernels import ref
    from repro.kernels.ttq_attn import (ttq_decode_attention,
                                        ttq_paged_decode_attention)
    from repro.kernels.ttq_gemm import ttq_gemm
    from repro.kernels.ttq_quantize import ttq_quantize

    d, dp, g, T = 3072, 9216, 32, 8
    B, H, Hkv, Dh, S, bs = 4, 24, 8, 128, 1024, 16
    ks = jax.random.split(key, 8)
    W = jax.random.normal(ks[0], (dp, d), jnp.float32).astype(jnp.bfloat16)
    D = jnp.exp(0.3 * jax.random.normal(ks[1], (d,), jnp.float32))
    x = jax.random.normal(ks[2], (T, d), jnp.float32).astype(jnp.bfloat16)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    def report(name, err, tol):
        log(f"kernel {name}: max_err={err:.3e} tol={tol:.0e} "
            f"{'ok' if err <= tol else 'FAIL'}")
        check(err <= tol, f"kernel check {name}: {err:.3e} > {tol:.0e}")

    hi = jax.default_matmul_precision("highest")
    for bits in (4, 8):
        pk, Sc, Z = jax.jit(lambda w, dd, b=bits: ref.ttq_quantize_ref(
            w, dd, bits=b, group_size=g))(W, D)
        y = ttq_gemm(x, pk, Sc, Z, 1.0 / D, bits=bits, group_size=g,
                     interpret=False, out_dtype=jnp.float32)
        with hi:
            y_r = ref.ttq_gemm_ref(x, pk, Sc, Z, bits=bits, group_size=g,
                                   dinv=1.0 / D)
        report(f"ttq_gemm int{bits} ({T}x{d} @ {d}x{dp})", rel(y, y_r),
               GEMM_TOL)

        pk_k, S_k, Z_k = ttq_quantize(W, D, bits=bits, group_size=g,
                                      interpret=False)
        u = np.asarray(unpack_weight(pk_k, d, bits), np.int64)
        u_r = np.asarray(unpack_weight(pk, d, bits), np.int64)
        step = int(np.abs(u - u_r).max())
        share = float((u != u_r).mean())
        s_err = max(rel(S_k, Sc), rel(Z_k, Z))
        log(f"kernel ttq_quantize int{bits} ({dp}x{d}): code_steps={step} "
            f"(tol {QCODE_TOL}) code_share={share:.2e} "
            f"(tol {QCODE_SHARE:.0e}) "
            f"scale_zero_err={s_err:.3e} (tol {QSCALE_TOL:.0e})")
        check(step <= QCODE_TOL and share <= QCODE_SHARE
              and s_err <= QSCALE_TOL, f"kernel check ttq_quantize int{bits}")

    q = jax.random.normal(ks[3], (B, H, 1, Dh), jnp.float32).astype(
        jnp.bfloat16)
    kf = jax.random.normal(ks[4], (B, Hkv, S, Dh), jnp.float32)
    vf = jax.random.normal(ks[5], (B, Hkv, S, Dh), jnp.float32)
    cur = jax.random.randint(ks[6], (B,), 0, S).astype(jnp.int32)
    nblk = S // bs
    nb = B * nblk + 1
    # a shuffled block table over pool blocks 1..nb-1 (0 is the sink)
    perm = np.asarray(jax.random.permutation(ks[7], nb - 1)) + 1
    table = jnp.asarray(perm.reshape(B, nblk), jnp.int32)
    for kv in ("int8", "int4"):
        c = KVCacheConfig(dtype=kv)
        kq, kscale = quantize_kv(kf, bits=c.bits)
        vq, vscale = quantize_kv(vf, bits=c.bits)
        o = ttq_decode_attention(q, kq, kscale, vq, vscale, cur, bits=c.bits,
                                 interpret=False)
        with hi:
            o_r = ref.kv_attn_ref(q, kq, kscale, vq, vscale, cur, bits=c.bits)
        report(f"ttq_attn dense {kv} (B{B} H{H}/{Hkv} S{S} Dh{Dh})",
               rel(o, o_r), ATTN_TOL)

        def to_pool(a):      # (B, Hkv, S, ·) → (nb, Hkv, bs, ·) by table
            blocks = a.reshape(B, Hkv, nblk, bs, -1).transpose(0, 2, 1, 3, 4)
            pool = jnp.zeros((nb,) + blocks.shape[2:], a.dtype)
            return pool.at[table.reshape(-1)].set(
                blocks.reshape((B * nblk,) + blocks.shape[2:]))
        pools = [to_pool(a) for a in (kq, kscale, vq, vscale)]
        o = ttq_paged_decode_attention(q, *pools, table, cur, bits=c.bits,
                                       interpret=False)
        with hi:
            o_r = ref.kv_paged_attn_ref(q, *pools, table, cur, bits=c.bits)
        report(f"ttq_attn paged {kv} (block {bs}, {nb} blocks)",
               rel(o, o_r), ATTN_TOL)


# ---------------------------------------------------------------- serving

def model_config(n_layers=None):
    import dataclasses

    from repro.configs import get
    cfg = get(ARCH)
    if n_layers is not None and n_layers != cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def build(cfg, params, mesh=1):
    from repro.launch.serve import build_engine, build_parser
    args = build_parser().parse_args(SERVE_FLAGS + ["--mesh", str(mesh)])
    return build_engine(args, cfg=cfg, params=params)


def guard_counters(eng):
    return {k: getattr(eng, k) for k in (
        "lane_faults", "calib_rejections", "requant_rejections",
        "admission_failures")}


def probe_logits(eng, cfg, seed):
    """Admit one prompt per ``PROBE_LENS`` entry (prefill + calibration +
    requant), then return host (prefill last-token logits on the fp tree,
    first-decode logits on the quantized tree) for those requests, read out
    of the engine's own admission-prefill and fused-decode programs."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab, size=n)]
               for n in PROBE_LENS]
    rids = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    eng.admit()
    slot_of = {req.rid: s for s, req in enumerate(eng.scheduler.slot_req)
               if req is not None}
    r = eng.runner
    lg_p = r.prefill_logits(eng.params, prompts)
    lg_d = r.first_decode_logits(eng.decode_params)
    lg_d = np.asarray(lg_d, np.float32)[[slot_of[i] for i in rids]]
    return np.asarray(lg_p, np.float32), lg_d


def program_kernels(eng):
    """Kernels in the decode program and in the requant family programs
    the engine compiled, as ({kernel: count}, {kernel: count})."""
    dec = kernel_calls(eng.runner.decode_program_text(eng.decode_params))
    req = {}
    for text in eng.qmodel.requant_program_texts().values():
        for k, v in kernel_calls(text).items():
            req[k] = req.get(k, 0) + v
    return dec, req


def check_kernels(eng, where):
    dec, req = program_kernels(eng)
    log(f"{where}: decode program kernels "
        + " ".join(f"{k}x{v}" for k, v in sorted(dec.items()))
        + "; requant programs kernels "
        + " ".join(f"{k}x{v}" for k, v in sorted(req.items())))
    for want in ("ttq_gemm", "ttq_paged_decode_attention"):
        check(want in dec, f"{where}: decode program has no {want} kernel")
    check("ttq_quantize" in req,
          f"{where}: requant programs have no ttq_quantize kernel")


def serve_run(cfg, dev, clock):
    import jax
    import numpy as np

    from repro.models import lm

    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.PRNGKey(SEED))
    eng = build(cfg, params)
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0
    log(f"setup: {setup_s:.1f}s (weights from seed {SEED} + engine); "
        f"kv pool {eng.num_blocks} blocks/layer of {eng.kvcfg.block_size}, "
        f"dtype {eng.kvcfg.dtype}; weights int{eng.policy.qcfg.bits} "
        f"g{eng.policy.qcfg.group_size} rank {eng.policy.rank} "
        f"packed={eng.policy.packed} ttq_gemm={eng.kncfg.use_pallas}")

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    rids = [eng.submit(list(rng.integers(1, cfg.vocab, size=int(n))),
                       max_new=MAX_NEW) for n in lens]
    c0 = clock.seconds
    t0 = time.perf_counter()
    first_requants = None
    for _ in range(10_000):                  # run_all's bound, stepped here
        if not (eng.scheduler.has_work() and eng.step()):
            break
        if first_requants is None and eng.n_requants:
            first_requants = eng.n_requants
    outs = eng.scheduler.results()
    run_s = time.perf_counter() - t0
    toks = sum(len(outs[r]) for r in rids)
    errors = {r: outs[r].error for r in rids if outs[r].error
              or outs[r].unfinished}
    guards = guard_counters(eng)
    log(f"traffic: {N_REQUESTS} requests, prompt lengths {lens.tolist()}, "
        f"max_new {MAX_NEW}: tokens={toks} wall={run_s:.1f}s "
        f"(compile {clock.seconds - c0:.1f}s of it) "
        f"requants={eng.n_requants} (first admission: {first_requants}) "
        f"prefill_chunks={eng.prefill_chunks} preemptions={eng.preemptions}")
    log("guards: " + " ".join(f"{k}={v}" for k, v in guards.items()))
    check(not errors, f"requests ended in error: {errors}")
    check(toks == N_REQUESTS * MAX_NEW, f"{toks} tokens, expected "
          f"{N_REQUESTS * MAX_NEW}")
    check(not any(guards.values()), f"guard events: {guards}")
    check(first_requants is not None and eng.n_requants > first_requants,
          "no online requantization after the first admission")

    check_kernels(eng, "one chip")

    lg_p, lg_d = probe_logits(eng, cfg, SEED)
    finite = bool(np.isfinite(lg_p).all() and np.isfinite(lg_d).all())
    log(f"logits: prefill {lg_p.shape} and first decode {lg_d.shape} "
        f"finite={finite}; requants={eng.n_requants}")
    check(finite, "non-finite logits")
    check(not any(guard_counters(eng).values()),
          f"guard events: {guard_counters(eng)}")
    log(f"peak_bytes_in_use={peak_bytes(dev)}")


def describe(cfg, cut=""):
    log(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"tied_embeddings={cfg.tie_embeddings} (published widths; "
        f"{cut or 'no cut'})")


def serve_fitting(cfg, dev, clock):
    """``serve_run`` at the published depth; if the chip's memory overflows,
    print the peak and the failing program, then run again cut to
    ``CUT_LAYERS`` layers."""
    import jax
    try:
        return serve_run(cfg, dev, clock)
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        what = " | ".join(str(e).splitlines()[:3])[:1500]
    gc.collect()           # the failed run's arrays died with its frames
    log(f"memory: {cfg.n_layers} layers overflow the chip: "
        f"peak_bytes_in_use={peak_bytes(dev)}; {what}")
    cut = model_config(CUT_LAYERS)
    describe(cut, f"depth cut {cfg.n_layers} -> {CUT_LAYERS}: "
                  f"{cfg.n_layers} layers did not fit")
    return serve_run(cut, dev, clock)


def kernel_calls(text):
    """{jitted kernel entry: count} over the tpu_custom_call ops of a
    compiled program's ``text``: the innermost ``jit(name)`` before
    ``pallas_call`` in each op's name (``.../shard_map/vmap(jit(
    ttq_quantize))/pallas_call`` → ``ttq_quantize``)."""
    import re
    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        op = re.search(r'op_name="([^"]*)pallas_call', line)
        m = re.findall(r"jit\((\w+)\)", op.group(1)) if op else []
        name = m[-1] if m else "?"
        out[name] = out.get(name, 0) + 1
    return out


def mesh_compare(cfg, n_chips):
    """Prefill and first-decode logits: (1, n) TP mesh vs one chip."""
    import jax
    import numpy as np

    from repro.models import lm

    params = lm.init_params(cfg, jax.random.PRNGKey(SEED))
    got = {}
    for n in (1, n_chips):
        eng = build(cfg, params, mesh=n)
        got[n] = probe_logits(eng, cfg, SEED)
        guards = guard_counters(eng)
        log(f"mesh (1, {n}): requants={eng.n_requants} guards: "
            + " ".join(f"{k}={v}" for k, v in guards.items()))
        check(eng.n_requants > 0, f"mesh {n}: no requantization")
        check(not any(guards.values()), f"mesh {n} guard events: {guards}")
        check_kernels(eng, f"mesh (1, {n})")
        del eng
        gc.collect()
    ok = True
    for i, what in enumerate(("prefill", "first-decode")):
        a, b = got[1][i], got[n_chips][i]
        fin = bool(np.isfinite(a).all() and np.isfinite(b).all())
        err = float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-30))
        agree = float((a.argmax(-1) == b.argmax(-1)).mean())
        log(f"{what} logits mesh 1 vs {n_chips}: max_err={err:.3e} "
            f"(tol {MESH_TOL:.0e}, relative to max |logit| "
            f"{np.abs(a).max():.3f}) argmax_agreement={agree:.2f} "
            f"finite={fin}")
        ok = ok and fin and err <= MESH_TOL
    check(ok, f"mesh 1 vs {n_chips} logits disagree")


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the (1, 4) mesh vs one-chip comparison")
    args = ap.parse_args(argv)
    try:
        import jax

        devs = tpu_devices()
        from repro.launch.cache import enable_compile_cache
        cache = enable_compile_cache()
        clock = CompileClock()
        dev = devs[0]
        log(f"device: platform={dev.platform} kind={dev.device_kind} "
            f"count={len(devs)} jax={jax.__version__} compile_cache={cache}")
        if len(devs) < args.chips:
            raise SmokeFailure(f"--chips {args.chips} needs {args.chips} "
                               f"devices, found {len(devs)}")
        cfg = model_config()
        describe(cfg)
        if args.chips > 1:
            mesh_compare(cfg, args.chips)
        else:
            kernel_checks(jax.random.PRNGKey(SEED))
            serve_fitting(cfg, dev, clock)
        log(f"compile: {clock.seconds:.1f}s total, "
            f"{clock.cache_hits} persistent-cache hits")
        if FAILED:
            raise SmokeFailure("; ".join(FAILED))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
