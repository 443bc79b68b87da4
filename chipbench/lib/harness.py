"""One run of one cell: set-up, the measured window, the metrics, the check.

Set-up (counted in ``setup_s``): the weights from the seed on the device,
the engine through ``launch/serve.build_engine`` with the configuration's
serving flags, and a warm-up that runs every program shape the cell's
traffic can reach (each prompt bucket at each admission-group size the mix
names, each chunk offset, both decode programs, the requant family
programs).  Then ``TTQServer`` serves the traffic for ``--seconds``
(closed loop: once every client holds a stream); ``--trace 1`` traces a
sub-window in the middle.  After the window: the kernels in the decode
and requant programs, the peak device memory, the metrics; then the
program's state is freed and the plain reference (lib/reference.py)
judges a seeded sample of the tokens served in the run: of requests that
finished, and of those cut off at the window's close.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

from . import clients, manifest, reference, spans, stats, trace, traffic
from . import weights
from .dims import ACTS, dims

TRACE_SECONDS = 8.0      # traced sub-window (the middle of the window)
CACHE_DIR = os.path.join(manifest.ROOT, ".jax_cache")
now = time.perf_counter


class NoChip(RuntimeError):
    """No accelerator of the kind the cell needs."""


def log(msg: str):
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


class CompileMonitor:
    """Backend compiles (and persistent-cache hits) JAX reports."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def configure_jax(cache_dir: str = CACHE_DIR):
    """Persistent compile cache at a fixed path inside the checkout, every
    program written to it however short its compile."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chips(n: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def peaks_of(kind: str) -> dict:
    table = manifest.load_json(os.path.join(manifest.BENCH_DIR,
                                            "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ------------------------------------------------------------- the program

def model_config(conf: dict):
    """The program's ModelConfig: the repository's architecture with the
    configuration file's sizes."""
    from repro.configs import get

    d = dims(conf)
    base = get(conf["arch"])
    cfg = dataclasses.replace(
        base, n_layers=d.L, d_model=d.D, n_heads=d.H, n_kv_heads=d.Hkv,
        head_dim=d.hd, d_ff=d.F, vocab=d.V, tie_embeddings=d.tied,
        rope_theta=d.theta)
    want = dict(family="dense", mlp="plain", norm="layer", pos="rope",
                act=ACTS[conf["hidden_act"]], qk_norm=False,
                attn_soft_cap=0.0)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['arch']} runs {got}, the configuration "
                         f"states {want}")
    return cfg


def build(conf: dict, cfg, params, bits=None):
    from repro.launch.serve import build_engine, build_parser

    s = conf["serving"]
    flags = ["--arch", conf["arch"], *s["flags"], "--slots", str(s["slots"]),
             "--max-len", str(s["max_len"])]
    if bits is not None:
        flags += ["--bits", str(bits)]
    return build_engine(build_parser().parse_args(flags), cfg=cfg,
                        params=params)


def warm_up(eng, mix: dict, seed: int):
    """Run every shape the mix can reach once: each prompt bucket at each
    admission-group size up to the mix's ``warm_groups`` (each admission
    also requantizes), the chunk offsets of the longest prompt, the K-step
    decode program and the K=1 program the degradation ladder swaps in.
    Then cancel everything and drop the warm-up prompts' cached blocks,
    which no request can hit."""
    ec = eng.ecfg
    rng = np.random.default_rng([seed, 1])   # a stream apart from traffic's
    V = eng.cfg.vocab
    shp = traffic.prefill_shapes(mix, ec.prompt_buckets, ec.prefill_chunk)
    new = 2 * max(1, ec.decode_chunk) + 2

    def prompt(n):
        return rng.integers(1, V, size=n).tolist()

    groups = min(int(mix.get("warm_groups", ec.max_slots)), ec.max_slots)
    for b in shp["buckets"]:
        for n in range(groups, 0, -1):
            rids = [eng.submit(prompt(b), max_new=new) for _ in range(n)]
            eng.admit()
            for r in rids:
                eng.cancel(r)
    rids = []
    if shp["chunk_starts"]:
        rids.append(eng.submit(prompt(traffic.bounds(mix)[1]), max_new=new))
    short = shp["buckets"][0] if shp["buckets"] else ec.prefill_chunk
    while len(rids) < ec.max_slots:
        rids.append(eng.submit(prompt(short), max_new=new))
    for _ in range(64):
        eng.step()
        if not eng.scheduler.prefilling:
            break
    eng.step()
    eng.runner.decode_block(eng.decode_params, None, small_chunk=True)
    for r in rids:
        eng.cancel(r)
    if eng.allocator is not None:
        eng.allocator.drop_cached()
    for _ in range(4):
        eng.step()


def kernel_calls(text: str) -> dict:
    """{jitted kernel entry: count} over the ``tpu_custom_call`` ops of a
    compiled program's text: the innermost ``jit(name)`` before
    ``pallas_call`` in each op's name."""
    out: dict = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        op = re.search(r'op_name="([^"]*)pallas_call', line)
        m = re.findall(r"jit\((\w+)\)", op.group(1)) if op else []
        name = m[-1] if m else "?"
        out[name] = out.get(name, 0) + 1
    return out


def program_kernels(eng):
    dec = kernel_calls(eng.runner.decode_program_text(eng.decode_params))
    req: dict = {}
    for text in eng.qmodel.requant_program_texts().values():
        for k, v in kernel_calls(text).items():
            req[k] = req.get(k, 0) + v
    return dec, req


def engine_counts(eng) -> dict:
    return {"requant_wall_s": eng.requant_wall_s,
            "n_requants": eng.n_requants,
            "host_syncs": eng.host_syncs,
            "prefill_chunks": eng.prefill_chunks,
            "preemptions": eng.preemptions,
            "degrade_events": eng.degrade_events,
            "lane_faults": eng.lane_faults,
            "calib_rejections": eng.calib_rejections,
            "requant_rejections": eng.requant_rejections,
            "admission_failures": eng.admission_failures}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b if b[k] != a.get(k, 0)}


# -------------------------------------------------------------- the window

@dataclasses.dataclass
class Window:
    records: list
    t0: float
    t1: float
    extra: float                 # closed: fill seconds; open: generator lag
    marks: dict                  # name → (time, counters, engine counts)


def serve(eng, cell, reqs, seconds: float, traced: bool, logdir: str,
          counters) -> Window:
    """Start a TTQServer over ``eng`` and drive the cell's traffic."""
    from repro.serving.server import TTQServer

    mix = cell.traffic
    marks: dict = {}

    def mark(name):
        marks[name] = (now(), counters.snapshot(), engine_counts(eng))

    def traced_window(t_len):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                mark("trace0")
                time.sleep(t_len)
                mark("trace1")
        finally:
            jax.profiler.stop_trace()

    async def main():
        limit = len(reqs) + int(mix.get("clients", 0)) + 1
        server = TTQServer(eng, max_concurrent=limit)
        loop = asyncio.get_running_loop()
        tracer = None

        def on_open():
            nonlocal tracer
            mark("open")
            loop.call_later(seconds, mark, "close")
            if traced:
                t_len = min(TRACE_SECONDS, seconds)
                delay = (seconds - t_len) / 2

                async def later():
                    await asyncio.sleep(delay)
                    await loop.run_in_executor(None, traced_window, t_len)
                tracer = asyncio.ensure_future(later())

        async with server:
            if mix["loop"] == "open":
                recs, t0, t1, extra = await clients.open_loop(
                    server, reqs, seconds, on_open)
            else:
                recs, t0, t1, extra = await clients.closed_loop(
                    server, reqs, int(mix["clients"]), seconds, on_open,
                    float(mix.get("stagger_s", 0.0)))
            if tracer is not None:
                await tracer
        if server.error is not None:
            raise RuntimeError(f"engine worker crashed: {server.error!r}")
        return Window(recs, t0, t1, extra, marks)

    return asyncio.run(main())


# ----------------------------------------------------------------- metrics

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees (``metrics/<name>.py``)."""
    d: object                # lib.dims.Dims
    slots: int
    bits: int
    group: int
    chips: int
    peaks: dict
    window_s: float
    counters: dict           # harness counters over the (traced) window
    engine: dict             # engine counters over the (traced) window
    summary: dict            # lib.stats.summarize over the (traced) window
    trace: object = None     # lib.trace.Trace | None


def end_to_end(cell, summary: dict, window_s: float, setup_s: float):
    """The cell's end-to-end metrics by name: ``output_tok_s``,
    ``setup_s``, and ``ttft_p<q>_ms`` / ``itl_p<q>_ms``, the q-th
    percentile of the window's times to first token / inter-token gaps."""
    def value(name):
        if name == "output_tok_s":
            return summary["tokens"] / window_s
        if name == "setup_s":
            return setup_s
        m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
        if m is None:
            raise KeyError(f"no end-to-end metric {name!r}")
        return 1e3 * stats.pct(summary[m.group(1)], int(m.group(2)) / 100)
    return {m["name"]: {"value": value(m["name"]), "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, ctx: Context):
    out = {}
    for m in cell.per_layer:
        v = manifest.load_metric(m["name"], cell.bench_dir).read(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ------------------------------------------------------------------- check

def sample(records, seed: int, limits: dict):
    """Requests to judge by what they were served: the finished request
    with the most tokens, and the request cut off at the close with the
    most, then others of either kind drawn by the seed until
    ``sample_tokens`` served tokens or ``sample_requests`` requests."""
    def longest(rs):
        return [max(rs, key=lambda r: (len(r.tokens), -r.index))] if rs \
            else []
    served = [r for r in records if r.tokens and not r.error]
    out = longest([r for r in served if r.done]) + longest(
        [r for r in served if not r.done])
    rest = [r for r in served if all(r is not o for o in out)]
    rng = np.random.default_rng(seed)
    n = sum(len(r.tokens) for r in out)
    for i in rng.permutation(len(rest)):
        if n >= limits["sample_tokens"] or \
                len(out) >= limits["sample_requests"]:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def judge(d, seed: int, picked, S: int):
    """Widest gap of a served token's logit below the reference's best,
    over the picked requests (weights made again from the seed)."""
    w = weights.make(d, seed)
    widest = 0.0
    for r in picked:
        g = reference.gaps(d, w, r.prompt, r.tokens, S)
        widest = max(widest, float(g.max()))
    del w
    return widest


def passes(check: dict) -> bool:
    """A compared number against its limit; no number (no served token to
    judge) fails."""
    v, lim = check["value"], check["limit"]
    if v is None:
        return False
    return v <= lim if check["op"] == "<=" else v >= lim


def free_device():
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


# -------------------------------------------------------------------- run

def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        monitor: CompileMonitor, *, require_tpu=True, bits=None,
        peaks=None):
    """One run; returns (result dict, checks dict)."""
    import jax

    conf, mix = cell.config, cell.traffic
    devs = chips(cell.chips, require_tpu)
    dev = devs[0]
    peaks = peaks if peaks is not None else peaks_of(dev.device_kind)
    d = dims(conf)
    cfg = model_config(conf)
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; cell "
        f"{cell.name} seed {seed} seconds {seconds} trace {int(traced)}")

    params = weights.make(d, seed)
    jax.block_until_ready(params)
    eng = build(conf, cfg, params, bits)
    t = now()
    c0 = monitor.seconds
    warm_up(eng, mix, seed)
    log(f"weights + engine + warm-up: {now() - t_start:.1f}s (warm-up "
        f"{now() - t:.1f}s, compiling {monitor.seconds - c0:.1f}s, "
        f"{monitor.compiles} compiles, {monitor.cache_hits} cache hits)")

    counters = spans.Counters()
    undo = spans.install(eng, counters, traced)
    reqs = traffic.requests(mix, seed, d.V)
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    compiles0 = monitor.compiles
    win = serve(eng, cell, reqs, seconds, traced, logdir, counters)
    compiles_in_window = monitor.compiles - compiles0
    undo()
    setup_s = win.marks["open"][0] - t_start
    full = stats.summarize(win.records, win.t0, win.t1)
    log(f"window {win.t1 - win.t0:.2f}s: attempted {full['attempted']} "
        f"failed {full['failed']} tokens {full['tokens']}; "
        f"{'fill' if mix['loop'] == 'closed' else 'generator lag'} "
        f"{win.extra:.3f}s; compiles in window {compiles_in_window}")

    dec, req = program_kernels(eng)
    peak = max(int((x.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for x in devs)
    eng_w = delta(win.marks["open"][2], win.marks["close"][2])
    eng_w.update(delta(win.marks["open"][1], win.marks["close"][1]))
    log("over the window: " + " ".join(
        f"{k}={v:.4g}" for k, v in sorted(eng_w.items()))
        + f"; degrade level at close {eng.degrade_level}")

    a, b = ("trace0", "trace1") if traced else ("open", "close")
    tr = None
    if traced:
        tr = trace.load(trace.find_xplane(logdir),
                        [name for _, name in spans.CALLS])
    shutil.rmtree(logdir, ignore_errors=True)
    ctx = Context(
        d=d, slots=eng.ecfg.max_slots, bits=eng.policy.qcfg.bits,
        group=eng.policy.qcfg.group_size,
        chips=len(devs), peaks=peaks,
        window_s=win.marks[b][0] - win.marks[a][0],
        counters=delta(win.marks[a][1], win.marks[b][1]),
        engine=delta(win.marks[a][2], win.marks[b][2]),
        summary=stats.summarize(win.records, win.marks[a][0],
                                win.marks[b][0]), trace=tr)
    layer = per_layer(cell, ctx)
    e2e = end_to_end(cell, full, win.t1 - win.t0, setup_s)
    log("end-to-end: " + json.dumps(e2e) + "; time to first token "
        "p50/p90/p95 " + "/".join(
            f"{1e3 * stats.pct(full['ttft'], q):.4f}"
            for q in (0.5, 0.9, 0.95)) + " ms; itl p50/p90/p95 " + "/".join(
            f"{1e3 * stats.pct(full['itl'], q):.4f}"
            for q in (0.5, 0.9, 0.95)) + " ms")
    log("per-layer: " + json.dumps(layer))

    picked = sample(win.records, seed, cell.limits)
    S = reference.padded_len(traffic.bounds(mix)[2])
    del eng, params
    free_device()
    t = now()
    widest = judge(d, seed, picked, S) if picked else None
    log(f"reference over {len(picked)} requests "
        f"({sum(len(r.tokens) for r in picked)} served tokens): "
        f"{now() - t:.1f}s")

    checks = {
        "max_gap": {"value": widest, "limit": cell.limits["max_gap"],
                    "op": "<="},
        "decode_ttq_gemm": {"value": dec.get("ttq_gemm", 0), "limit": 1,
                            "op": ">="},
        "decode_ttq_paged_attention": {
            "value": dec.get("ttq_paged_decode_attention", 0), "limit": 1,
            "op": ">="},
        "requant_ttq_quantize": {"value": req.get("ttq_quantize", 0),
                                 "limit": 1, "op": ">="},
    }
    correct = all(passes(c) for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": full["attempted"],
              "failed": full["failed"],
              "metrics": layer if traced else e2e, "device": device}
    if traced:
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    result["checks"] = checks
    return result, checks


def main(args, t_start: float) -> int:
    """``run.py``'s body: one run, the result as the last line of standard
    output, the compared numbers as the last lines of standard error."""
    try:
        cell = manifest.resolve(args.workload)
        import jax  # noqa: F401  (after the manifest: a bad name fails fast)

        configure_jax()
        monitor = CompileMonitor()
        result, checks = run(cell, args.seed, float(args.seconds),
                             bool(args.trace), t_start, monitor)
    except NoChip as e:
        log(f"FAILED: {e}")
        return 2
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} {c['op']} {c['limit']} "
              f"{'ok' if passes(c) else 'FAIL'}", file=sys.stderr, flush=True)
    return 0
