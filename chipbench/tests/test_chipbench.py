"""CPU tests of the benchmark harness (``chipbench/``).

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The arithmetic (trace reduction, window statistics, FLOP and byte
counts, the generator) is checked on small hand-made inputs; the run as a
whole on a tiny configuration of the same dense GQA path, where the
comparison that decides ``correct`` must pass the program, and fail the
control (the program at 2-bit weights) and a fault planted where tokens
are produced.  On the CPU the kernel checks always fail (the Pallas
kernels run interpreted, with no ``tpu_custom_call`` to find), so those
tests read the ``max_gap`` check itself.
"""
import asyncio
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

from lib import clients, dims, flops, harness, manifest, stats  # noqa: E402
from lib import trace, traffic  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TINY_LIMIT = 0.8     # widest gap at the tiny size: sound runs read 0.2-0.45,
#                      the 3-bit program 0.9, the 2-bit program 1.9


# ------------------------------------------------------------------ trace

def _tr():
    ops = [trace.Event("%while.3 = (s32[]) while(...)", 0, 50),
           trace.Event("%fusion.1 = f32[8] fusion(...)", 0, 10),
           trace.Event("%ttq_gemm.28 = f32[16,9216] custom-call(...)", 5, 20),
           trace.Event("%fusion.2 = f32[8] fusion(...)", 40, 50),
           trace.Event("%ttq_paged_decode_attention.1 = f32[8] custom-call",
                       42, 44),
           trace.Event("%vmap_jit_ttq_quantize__.1 = (s32[8]) custom-call",
                       80, 95)]
    mods = [trace.Event("jit__unknown(1)", 0, 50),
            trace.Event("jit__unknown(2)", 80, 95)]
    host = [trace.Event("engine.step", 0, 100),
            trace.Event("runner.decode_block", 18, 45),
            trace.Event("engine.admit", 50, 90)]
    return trace.Trace((0.0, 100.0), {"/device:TPU:0": ops},
                       {"/device:TPU:0": mods}, host)


def test_trace_busy_union_kernels_and_gaps():
    tr = _tr()
    assert trace.union([(0, 10), (5, 20), (40, 50)]) == [(0, 20), (40, 50)]
    assert trace.busy_s(tr) == pytest.approx(65e-9)
    assert trace.idle_share(tr) == pytest.approx(35.0)
    assert trace.kernel_time(tr, "ttq_gemm") == (pytest.approx(15e-9), 1)
    assert trace.kernel_time(tr, "ttq_quantize") == (pytest.approx(15e-9), 1)
    assert trace.program_time(tr, "ttq_paged_decode_attention") == \
        (pytest.approx(50e-9), 1)
    gaps = trace.idle_gaps(tr)
    assert [g[0] for g in gaps] == ["engine.admit", "engine.step"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 5e-9])
    top = dict(trace.top_ops(tr))
    assert top["ttq_gemm"] == pytest.approx(15e-9)
    assert top["fusion"] == pytest.approx(20e-9)
    assert "while" not in top


# ------------------------------------------------------ generator, window

MIX = {"loop": "open", "arrivals": {"law": "poisson", "rate_per_s": 50.0},
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 48},
       "output": {"dist": "uniform", "min": 4, "max": 8}, "pool": 32}


def test_generator_is_seeded_and_offers_the_same_work():
    a = traffic.requests(MIX, 2**33 + 5, 512)
    b = traffic.requests(MIX, 2**33 + 5, 512)
    c = traffic.requests(MIX, 7, 512)
    assert [(r.prompt, r.max_new, r.gap_s) for r in a] == \
        [(r.prompt, r.max_new, r.gap_s) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    key = sorted((len(r.prompt), r.max_new, r.gap_s) for r in a)
    assert key == sorted((len(r.prompt), r.max_new, r.gap_s) for r in c)
    assert all(8 <= len(r.prompt) <= 48 and 4 <= r.max_new <= 8 for r in a)
    fixed = dict(MIX, order="fixed")
    a = traffic.requests(fixed, 2**33 + 5, 512)
    c = traffic.requests(fixed, 7, 512)
    assert [(len(r.prompt), r.max_new, r.gap_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.gap_s) for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)
    wide = dict(MIX, prompt={"dist": "uniform", "min": 8, "max": 100})
    assert traffic.bounds(wide) == (9, 99, 99 + 8)
    shp = traffic.prefill_shapes(wide, (16, 32, 64, 128, 256), 32)
    assert shp == {"buckets": [16, 32], "chunk_starts": [0, 32, 64, 96]}


class _StallServer:
    """Streams ``max_new`` tokens 1 ms apart; the third request sent waits
    ``stall`` seconds before its first token."""

    def __init__(self, stall):
        self.stall, self.n = stall, 0

    async def generate(self, prompt, max_new):
        self.n += 1
        if self.n == 3:
            await asyncio.sleep(self.stall)
        for i in range(max_new):
            await asyncio.sleep(0.001)
            yield i


def test_open_loop_times_from_the_due_time():
    reqs = traffic.requests(MIX, 3, 512)
    recs, t0, t1, lag = asyncio.run(
        clients.open_loop(_StallServer(0.25), reqs, 0.6))
    s = stats.summarize(recs, t0, t1)
    assert s["attempted"] == len(recs) > 10
    assert lag < 0.05
    ttft = sorted(s["ttft"])
    assert ttft[-1] >= 0.25               # the stall is in the tail
    assert ttft[len(ttft) // 2] < 0.05


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))
    return path


def test_mixes_with_other_laws_are_data_alone(tmp_path):
    """Bursts, shared system prompts, a mixture of lengths and a recorded
    table of arrivals: each a traffic file, read by the one generator."""
    law = {"dist": "mixture", "parts": [
        dict(MIX["prompt"], weight=3),
        {"dist": "uniform", "min": 400, "max": 500, "weight": 1}]}
    burst = _write(str(tmp_path), "traffic/burst.json", dict(
        MIX, arrivals={"law": "onoff", "rate_per_s": 50.0, "on_s": 0.2,
                       "off_s": 1.0},
        prompt=law, prefix={"tokens": 16, "count": 2}, order="fixed"))
    mix = traffic.load(burst)
    reqs = traffic.requests(mix, 2**31 + 9, 512)
    assert len(reqs) == 32
    assert sum(r.gap_s >= 1.0 for r in reqs) == 3      # 32 over 10 per burst
    assert sum(len(r.prompt) >= 416 for r in reqs) == 8
    assert reqs[0].prompt[:16] == reqs[2].prompt[:16] != reqs[1].prompt[:16]
    lo, hi, top = traffic.bounds(mix)
    assert lo >= 16 + 8 and hi <= 16 + 500 and top <= hi + 8
    _write(str(tmp_path), "traffic/recorded.csv",
           "gap_s,prompt,output\n0.0,20,4\n0.5,40,6\n0.01,10,5\n")
    table = _write(str(tmp_path), "traffic/recorded.json", {
        "loop": "open", "arrivals": {"law": "table",
                                     "file": "recorded.csv"}})
    reqs = traffic.requests(traffic.load(table), 3, 512)
    assert [(len(r.prompt), r.max_new, r.gap_s) for r in reqs] == \
        [(20, 4, 0.0), (40, 6, 0.5), (10, 5, 0.01)]


def test_window_counts_in_flight_requests():
    R = stats.Record
    recs = [R(0, 10, 4, due=0.0, times=[0.5, 1.0, 1.5, 2.0], done=True),
            R(1, 20, 4, due=1.0, times=[1.2, 1.4]),        # in flight
            R(2, 30, 4, due=2.0),                           # no token yet
            R(3, 10, 4, due=-5.0, times=[-4.0], error="x")]  # before
    s = stats.summarize(recs, 1.0, 3.0)
    assert s["attempted"] == 3 and s["failed"] == 0
    assert s["tokens"] == 5                    # 1.0 1.5 2.0 | 1.2 1.4
    assert sorted(s["itl"]) == pytest.approx([0.2, 0.5, 0.5])
    assert sorted(s["ttft"]) == pytest.approx([0.2, 1.0])  # 3.0 − 2.0
    assert s["decode_ctx"] == [11, 12, 13, 21]
    assert s["prefill_plens"] == [20]
    assert stats.pct([1, 2, 3, 4], 0.5) == 2.5
    assert stats.pct(list(range(101)), 0.95) == pytest.approx(95.0)
    names = ("output_tok_s", "itl_p50_ms", "ttft_p95_ms", "setup_s")
    cell = manifest.Cell("c", {}, {}, {}, 1, [
        {"name": n, "unit": "u"} for n in names], [])
    e2e = harness.end_to_end(cell, s, 2.0, 7.0)
    assert [e2e[n]["value"] for n in names] == pytest.approx(
        [2.5, 500.0, 1e3 * stats.pct([0.2, 1.0], 0.95), 7.0])


def test_the_check_judges_finished_and_cut_off_requests():
    R = stats.Record
    recs = [R(i, 10, 8, 0.0, tokens=[1] * n, done=done, error=err)
            for i, (n, done, err) in enumerate(
                [(8, True, ""), (3, True, ""), (6, False, ""),
                 (2, False, ""), (9, False, "x"), (0, False, "")])]
    picked = harness.sample(recs, 2**31 + 1, {"sample_tokens": 100,
                                              "sample_requests": 16})
    assert [r.index for r in picked[:2]] == [0, 2]
    assert sorted(r.index for r in picked) == [0, 1, 2, 3]
    picked = harness.sample(recs, 5, {"sample_tokens": 10,
                                      "sample_requests": 16})
    assert [r.index for r in picked] == [0, 2]


# ----------------------------------------------------------- FLOPs, bytes

D = dims.Dims(L=2, D=64, H=4, Hkv=2, hd=16, F=128, V=100, tied=True,
              theta=1e4, eps=1e-5, act="relu")


def test_flop_and_byte_counts_by_hand():
    gemm = manifest.load_metric("ttq_gemm_roofline")
    f, b = gemm.cost(B=8, d_in=64, d_out=128, bits=4, g=32)
    assert f == 2 * 8 * 64 * 128
    assert b == 64 * 128 / 2 + 2 * 4 * 2 * 128 + 4 * 64 + 2 * 8 * (64 + 128)
    quant = manifest.load_metric("ttq_quantize_roofline")
    f, b = quant.cost(d_out=128, d_in=64, bits=4, g=32)
    assert f == 5 * 128 * 64
    assert b == 128 * 64 * (2 + 0.5 + 0.25) + 4 * 64
    assert quant.families(D) == {(64, 64): 4, (32, 64): 4, (128, 64): 2,
                                 (64, 128): 2}
    per_layer = 64 * 64 * 2 + 32 * 64 * 2 + 128 * 64 * 2
    assert flops.weight_flops(D) == 2 * 2 * per_layer
    assert flops.decode_flops(D, 10) == \
        2 * 2 * per_layer + 2 * 100 * 64 + 4 * 2 * 4 * 16 * 10
    assert flops.prefill_flops(D, 3) == \
        3 * 2 * 2 * per_layer + 4 * 2 * 4 * 16 * 6 + 2 * 100 * 64


# ---------------------------------------------------------- manifest

def test_every_cell_resolves_its_files():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        cell = manifest.resolve(w["name"], m)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        assert cell.limits["max_gap"] > 0
        assert any(e["name"] == "setup_s" for e in cell.end_to_end)
        assert cell.per_layer
        for p in cell.per_layer:
            assert callable(manifest.load_metric(p["name"]).read)
            assert p["moves"] in {e["name"] for e in cell.end_to_end}


def _tiny_root(root):
    """A checkout-shaped directory with one tiny configuration and two
    tiny cells, made only of new files."""
    bench = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"))
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    conf = manifest.load_json(os.path.join(BENCH, "configs",
                                           "minitron-4b.json"))
    conf.update(name="tiny", num_hidden_layers=2, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                intermediate_size=256, vocab_size=512)
    conf["serving"] = dict(conf["serving"], slots=4, max_len=128)
    flags = list(conf["serving"]["flags"])
    flags[flags.index("--prefill-chunk") + 1] = "32"
    conf["serving"]["flags"] = flags
    law = {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
           "max": 48}
    files = {
        "configs/tiny.json": conf,
        "traffic/tiny-closed.json": {
            "loop": "closed", "clients": 4, "prompt": law, "pool": 64,
            "output": {"dist": "uniform", "min": 8, "max": 16}},
        "limits/tiny.decode.json": {"max_gap": TINY_LIMIT,
                                    "sample_tokens": 64,
                                    "sample_requests": 8},
    }
    for rel, obj in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)
    m = manifest.load_manifest()
    m["configs"] = [{"name": "tiny", "source": "test",
                     "file": "chipbench/configs/tiny.json", "reduced": [],
                     "why": "test"}]
    m["workloads"] = [{"name": "tiny.decode", "config": "tiny",
                       "traffic": "tiny-closed", "chips": 1, "why": "test"}]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = ["tiny.decode"] if any(
                "decode" in w for w in e["workloads"]) else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def test_a_cell_made_of_new_files_is_found(tmp_path):
    root = _tiny_root(str(tmp_path))
    cell = manifest.resolve("tiny.decode", root=root)
    assert cell.config["hidden_size"] == 128
    assert cell.bench_dir == os.path.join(root, "chipbench")
    assert {m["name"] for m in cell.end_to_end} == {
        "output_tok_s", "itl_p95_ms", "setup_s"}
    assert "decode_occupancy" in {m["name"] for m in cell.per_layer}


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "minitron-4b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ------------------------------------------------- the comparison, whole

@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = _tiny_root(str(tmp_path_factory.mktemp("tiny")))
    return manifest.resolve("tiny.decode", root=root)


def _run(cell, seed, bits=None):
    harness.configure_jax(os.path.join(cell.bench_dir, ".jax_cache"))
    result, checks = harness.run(cell, seed, 4.0, False, time.perf_counter(),
                                 harness.CompileMonitor(), require_tpu=False,
                                 bits=bits, peaks=PEAKS)
    return result, checks["max_gap"]


def test_sound_run_passes_the_gap_check(tiny_cell):
    result, gap = _run(tiny_cell, 11)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert 0.0 <= gap["value"] <= gap["limit"]


def test_control_two_bit_weights_fail_the_gap_check(tiny_cell):
    _, gap = _run(tiny_cell, 11, bits=2)
    assert gap["value"] > gap["limit"]


def test_a_token_altered_where_produced_fails(tiny_cell, monkeypatch):
    build = harness.build

    def broken(*a, **kw):
        eng = build(*a, **kw)
        sch = eng.scheduler
        emit = sch.emit

        def altered(req, tok, now=0.0):
            if len(req.out) == 3:
                tok = (tok + 1) % eng.cfg.vocab
            emit(req, tok, now)
        sch.emit = altered
        return eng
    monkeypatch.setattr(harness, "build", broken)
    result, gap = _run(tiny_cell, 11)
    assert not result["correct"]
    assert gap["value"] > gap["limit"]
