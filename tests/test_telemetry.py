"""Telemetry (serving/telemetry.py): the spans and counters the serving
loop records about itself.

What is pinned: a span's self time is its total minus its children; the
lifecycle sums split each request's receipt-to-first-token time exactly,
once per request; the decode counters read the program that ran and agree
with the benchmark's outside wrapper; the views the benchmark reads
(``requant_wall_s``, ``host_syncs``) come from the telemetry; the server
stamps receipt before backpressure; and a profiler trace carries the spans
under the names the benchmark's trace reduction is given.
"""
import asyncio
import importlib.util
import os
import sys
import threading
import time

import jax
import pytest

from repro.core import NO_QUANT, ttq_policy
from repro.models import ModelConfig, lm
from repro.serving import (EngineConfig, Fault, FaultInjector, TTQEngine,
                           TTQServer, VirtualClock)
from repro.serving import telemetry
from repro.serving.telemetry import Telemetry

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=96, vocab=128)
PROMPTS = [[5, 9, 17, 3], [8, 8, 1], [100, 50, 25, 12, 6, 3], [7, 7, 7, 2]]
LIB = os.path.join(os.path.dirname(__file__), "..", "chipbench", "lib")


@pytest.fixture(scope="module")
def params():
    return lm.init_params(CFG, jax.random.PRNGKey(0))


def _bench_module(name):
    """A module of the benchmark's ``chipbench/lib`` by file path."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{name}", os.path.join(LIB, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _engine(params, policy=NO_QUANT, faults=(), clock=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_chunk", 3)
    return TTQEngine(CFG, params, policy, EngineConfig(**kw),
                     faults=FaultInjector(list(faults), clock=clock))


# ------------------------------------------------------------------ spans


def test_self_time_is_total_minus_children():
    tm = Telemetry()
    with tm.span("engine.round"):
        time.sleep(0.004)
        with tm.span("runner.decode"):
            time.sleep(0.003)
            with tm.span("runner.sync"):
                time.sleep(0.002)
        with tm.span("runner.decode"):
            time.sleep(0.001)
    s = tm.snapshot()
    assert s["engine.round.n"] == 1 and s["runner.decode.n"] == 2
    assert s["engine.round.self_s"] == pytest.approx(
        s["engine.round.total_s"] - s["runner.decode.total_s"], abs=1e-12)
    assert s["runner.decode.self_s"] == pytest.approx(
        s["runner.decode.total_s"] - s["runner.sync.total_s"], abs=1e-12)
    assert s["runner.sync.self_s"] == s["runner.sync.total_s"] >= 0.002
    assert s["engine.round.self_s"] >= 0.004


def test_names_are_fixed():
    tm = Telemetry()
    with pytest.raises(KeyError):
        tm.span("engine.nope")
    with pytest.raises(KeyError):
        tm.count("nope")
    assert set(tm.snapshot()) == (
        set(telemetry.COUNTERS)
        | {f"{s}.{k}" for s in telemetry.SPANS
           for k in ("n", "total_s", "self_s")})


def test_snapshot_is_consistent_under_a_concurrent_writer():
    """The worker thread writes while other threads snapshot: every
    snapshot sees whole updates only — the lifecycle sums of whole first
    tokens, a childless span's total with its equal self time."""
    tm = Telemetry()
    stop = threading.Event()
    bad = []

    def writer():
        while not stop.is_set():
            tm.first_token(0.0, 1.0, 3.0, 7.0)    # 1 + 2 + 4 per token
            with tm.span("sched.plan"):
                pass

    def reader():
        for _ in range(2000):
            s = tm.snapshot()
            n = s["first_tokens"]
            if (s["intake_wait_s"], s["queue_wait_s"], s["ingest_s"]) != \
                    (n, 2 * n, 4 * n) or \
                    s["sched.plan.self_s"] != s["sched.plan.total_s"]:
                bad.append(s)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer)]
        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads + readers:
            t.start()
        for t in readers:
            t.join(timeout=60)
        stop.set()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads + readers)
    assert tm.get("first_tokens") > 0 and not bad


# ---------------------------------------------------- per-request stamps


def test_lifecycle_sums_split_receipt_to_first_token(params):
    """On a virtual clock (one tick per round) the three sums add up to
    Σ first token − receipt exactly, each from its own stamps; a request
    that waited for the one slot shows queue wait."""
    clock = VirtualClock(start=1.0, tick=0.25)
    eng = _engine(params, clock=clock, max_slots=1)
    rids = [eng.submit(p, max_new=4, recv_t=0.5) for p in PROMPTS[:2]]
    clock.advance(0.5)
    rids.append(eng.submit(PROMPTS[2], max_new=4))   # no front end
    eng.run_all()
    reqs = [eng.finished[r] for r in rids]
    s = eng.telemetry.snapshot()
    assert s["first_tokens"] == 3
    assert reqs[2].recv_t == reqs[2].submit_t
    assert s["intake_wait_s"] == sum(r.submit_t - r.recv_t for r in reqs)
    assert s["queue_wait_s"] == sum(r.admit_t - r.submit_t for r in reqs)
    assert s["ingest_s"] == sum(r.first_t - r.admit_t for r in reqs)
    assert (s["intake_wait_s"] + s["queue_wait_s"] + s["ingest_s"]
            == sum(r.first_t - r.recv_t for r in reqs))
    assert reqs[1].admit_t > reqs[1].submit_t         # waited for the slot
    assert all(r.first_t == r.tok_times[0] for r in reqs)


def test_lane_fault_retry_counts_its_first_token_once(params):
    eng = _engine(params, faults=[Fault("decode.logits", rid=1, count=1)])
    rids = [eng.submit(p, max_new=6) for p in PROMPTS[:2]]
    out = eng.run_all()
    assert eng.lane_faults == 1 and not out[rids[1]].error
    s = eng.telemetry.snapshot()
    assert s["first_tokens"] == 2
    r = eng.finished[rids[1]]
    assert r.first_t < r.tok_times[0]       # the retry emitted again later


# ---------------------------------------------------------- decode counts


@pytest.mark.parametrize("K", [1, 3])
def test_decode_counters_read_the_program_that_ran(params, K):
    """decode_steps is K per dispatch (1 under the ladder's small
    program), decode_tokens the tokens landed in requests, and both agree
    with the benchmark's wrapper around the runner."""
    spans = _bench_module("spans")
    eng = _engine(params, decode_chunk=K)
    counters = spans.Counters()
    undo = spans.install(eng, counters, traced=False)
    rids = [eng.submit(p, max_new=9) for p in PROMPTS]
    out = eng.run_all()
    s0 = eng.telemetry.snapshot()
    assert s0["decode_steps"] == K * s0["decode_dispatches"] > 0
    assert s0["decode_slot_steps"] == s0["decode_steps"] * 2
    emitted = sum(len(out[r]) for r in rids)
    assert s0["decode_tokens"] == emitted - s0["first_tokens"]
    assert s0["decode_dispatches"] == counters.calls["runner.decode_block"]
    assert s0["decode_steps"] == counters.decode_steps
    assert s0["decode_slot_steps"] == counters.decode_slot_steps
    assert s0["decode_tokens"] == counters.decode_tokens
    assert s0["rounds"] == sum(s0[f"rounds_at_rung_{i}"] for i in range(4))
    eng.runner.decode_block(eng.decode_params, None, small_chunk=True)
    undo()
    s1 = eng.telemetry.snapshot()
    assert s1["decode_dispatches"] == s0["decode_dispatches"] + 1
    assert s1["decode_steps"] == s0["decode_steps"] + 1
    assert counters.decode_steps == s1["decode_steps"]


def test_page_counters_read_the_positions_of_the_one_sync(params):
    """One paged decode block adds steps × slots × table width to
    attn_pages_table and each live lane's pages, up to its position after
    the block, to attn_pages_live, from the one blocking read the block
    makes anyway."""
    import numpy as np

    eng = _engine(params, kv_dtype="int8", kv_paged=True, kv_block_size=16)
    eng.submit(list(range(1, 21)), max_new=30)   # one lane of two
    eng.step()
    s0 = eng.telemetry.snapshot()
    eng.runner.decode_block(eng.decode_params)
    s1 = eng.telemetry.snapshot()
    pos = np.asarray(eng.runner.pos)
    live = ~np.asarray(eng.runner.done)
    assert live.sum() == 1 and pos[live][0] > 16     # two pages, one lane
    assert s1["attn_pages_table"] - s0["attn_pages_table"] == 3 * 2 * 4
    assert s1["attn_pages_live"] - s0["attn_pages_live"] == 3 * 2
    assert s1["host_syncs"] - s0["host_syncs"] == 1
    assert s1["runner.sync.n"] - s0["runner.sync.n"] == 1


def test_ladder_rungs_counted_per_round(params):
    from repro.quant import GuardConfig

    eng = _engine(params, guard_cfg=GuardConfig(degrade_pressure=0.2,
                                                recover_pressure=0.05),
                  kv_dtype="int8", kv_paged=True, kv_block_size=16)
    for p in PROMPTS:
        eng.submit(p, max_new=8)
    eng.run_all()
    s = eng.telemetry.snapshot()
    assert eng.degrade_events > 0 and s["rounds_at_rung_0"] < s["rounds"]
    assert s["rounds"] == sum(s[f"rounds_at_rung_{i}"] for i in range(4))
    # rungs 2 and 3 decode at K=1
    assert s["decode_steps"] < 3 * s["decode_dispatches"]


# ------------------------------------------------- views and quant spans


def test_requant_and_host_sync_views_read_the_telemetry(params):
    eng = _engine(params, policy=ttq_policy(bits=8, group_size=32, rank=0))
    for p in PROMPTS:
        eng.submit(p, max_new=5)
    eng.run_all()
    s = eng.telemetry.snapshot()
    assert eng.n_requants > 0
    assert eng.requant_wall_s == s["requant.total_s"] > 0
    assert s["requant.n"] == eng.n_requants
    assert s["requant.run.n"] == s["requant.health.n"] == eng.n_requants
    assert s["requant.total_s"] >= (s["requant.run.total_s"]
                                    + s["requant.health.total_s"])
    assert s["quant.calibrate.n"] == s["runner.prefill.n"]
    # one blocking read per decode block and per admission group, as the
    # runner always counted them
    assert eng.host_syncs == s["host_syncs"] == s["runner.sync.n"] \
        == s["decode_dispatches"] + s["runner.prefill.n"]
    assert not hasattr(eng.qmodel, "last_health_drift")


# ----------------------------------------------------------- front end


def test_server_stamps_receipt_before_backpressure(params):
    """With one request admitted at a time, the second waits at the
    server's semaphore: that wait is intake, stamped before it."""
    eng = _engine(params, max_queue=8)

    async def main():
        async with TTQServer(eng, max_concurrent=1) as server:
            return await asyncio.gather(
                *[server.complete(p, max_new=4) for p in PROMPTS[:2]])

    outs = asyncio.run(main())
    assert all(len(o) == 4 and not o.error for o in outs)
    reqs = sorted(eng.finished.values(), key=lambda r: r.rid)
    assert all(r.recv_t <= r.submit_t for r in reqs)
    assert reqs[1].submit_t - reqs[1].recv_t >= \
        reqs[0].tok_times[-1] - reqs[0].submit_t
    s = eng.telemetry.snapshot()
    assert s["server.drain.n"] > 0 and s["first_tokens"] == 2
    assert s["intake_wait_s"] == pytest.approx(
        sum(r.submit_t - r.recv_t for r in reqs))


# ----------------------------------------------------------------- trace


def test_profiler_trace_carries_the_program_spans(params, tmp_path):
    """A CPU profiler trace of a few rounds, reduced by the benchmark's
    trace module with the telemetry's span names: the program's spans are
    on the host plane, per-request ones with their request ids."""
    trace = _bench_module("trace")
    eng = _engine(params, policy=ttq_policy(bits=8, group_size=32, rank=0))
    for p in PROMPTS[:2]:
        eng.submit(p, max_new=4)
    eng.step()                           # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in PROMPTS[2:]:             # one admission group: rids 2, 3
            eng.submit(p, max_new=4)
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    tr = trace.load(path, telemetry.SPANS)
    names = {e.name for e in tr.host}
    assert {"engine.round", "sched.plan", "runner.prefill", "runner.sync",
            "runner.decode", "sched.record", "quant.calibrate", "requant",
            "requant.run", "requant.health"} <= names
    assert names <= set(telemetry.SPANS)
    from jax.profiler import ProfileData

    meta = [str(v) for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host") for line in p.lines
            for e in line.events if e.name == "runner.prefill"
            for k, v in e.stats if k == "rids"]
    assert meta == ["2 3"]
