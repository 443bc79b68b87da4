"""Paged KV cache: pool/block-table layout, the paged Pallas kernel vs its
oracle, the block allocator + prefix trie lifecycle, and the engine-level
guarantees — paged ⇔ dense greedy equivalence (all KV dtypes, kernels
on/off), preemption + requeue, prefix-cache hits, and request cancellation
(DESIGN.md §8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KVCacheConfig, NO_QUANT
from repro.core.kvquant import quantize_kv
from repro.kernels import kv_paged_decode_attention
from repro.kernels.ref import gather_paged_kv, kv_attn_ref, kv_paged_attn_ref
from repro.models import ModelConfig, lm
from repro.serving import EngineConfig, TTQEngine
from repro.serving.blocks import SINK, BlockAllocator, chain_hashes

RNG = np.random.default_rng(7)

CFG = ModelConfig(name="paged-t", family="dense", n_layers=3, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=96, vocab=128)

PROMPTS = [[5, 9, 17, 3], [8, 8, 1], [100, 50, 25, 12, 6, 3], [7, 7, 7, 2]]


@pytest.fixture(scope="module")
def params():
    return lm.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, kv_dtype="bf16", paged=True, use_pallas=True, slots=2,
            **kw):
    pol = NO_QUANT.with_(kvcache=KVCacheConfig(dtype=kv_dtype, paged=paged,
                                               use_pallas=use_pallas))
    return TTQEngine(CFG, params, pol,
                     EngineConfig(max_slots=slots, max_len=64, **kw))


def _run(eng, prompts=PROMPTS, max_new=8):
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    outs = eng.run_all()
    return [outs[r] for r in rids]


_DENSE_REF = {}


def _dense_ref(params, kv_dtype):
    if kv_dtype not in _DENSE_REF:
        _DENSE_REF[kv_dtype] = _run(_engine(params, kv_dtype, paged=False))
    return _DENSE_REF[kv_dtype]


# ----------------------------------------------------------- paged kernel

def _small_table_case(bits, group_size):
    """Two lanes over a 9-block pool, 4-block rows ending in the sink."""
    B, Hkv, H, Dh, bs, NB = 2, 2, 4, 32, 16, 9
    pk = jnp.asarray(RNG.standard_normal((NB, Hkv, bs, Dh)).astype("float32"))
    pv = jnp.asarray(RNG.standard_normal((NB, Hkv, bs, Dh)).astype("float32"))
    kq, ks = quantize_kv(pk, bits=bits, group_size=group_size)
    vq, vs = quantize_kv(pv, bits=bits, group_size=group_size)
    bt = jnp.asarray([[3, 1, 4, SINK], [5, 2, SINK, SINK]], jnp.int32)
    pos = jnp.asarray([41, 17], jnp.int32)
    q = jnp.asarray(RNG.standard_normal((B, H, 1, Dh)).astype("float32"))
    return q, (kq, ks, vq, vs), bt, pos, [True, True]


def _tile_edge_case(Hkv, G, bits, group_size, sink_lane):
    """The cells' head shapes at head size 128 over a 40-page table: lanes
    at position 0, at the last token of the first compute tile, at the
    first of the next, and at the table's end (40 is no multiple of the
    tile's pages); rows past a lane's pages point at the sink, as the
    engine leaves them.  ``sink_lane`` adds a released lane: position
    ``max_len``, its whole row the sink."""
    from repro.kernels.ttq_attn import pages_per_tile

    Dh, bs, nblk = 128, 16, 40
    B = 5 if sink_lane else 4
    NB = B * nblk + 1
    pk = jnp.asarray(RNG.standard_normal((NB, Hkv, bs, Dh)).astype("float32"))
    pv = jnp.asarray(RNG.standard_normal((NB, Hkv, bs, Dh)).astype("float32"))
    pools = (*quantize_kv(pk, bits=bits, group_size=group_size),
             *quantize_kv(pv, bits=bits, group_size=group_size))
    T = pages_per_tile(pools, nblk, Dh) * bs
    assert T < nblk * bs and (nblk * bs) % T        # a partial last tile
    pos = [0, T - 1, T, nblk * bs - 1] + ([nblk * bs] if sink_lane else [])
    bt = np.full((B, nblk), SINK, np.int32)
    ids = RNG.permutation(np.arange(1, NB)).reshape(B, nblk)
    for b, p in enumerate(pos[:4]):
        bt[b, :p // bs + 1] = ids[b, :p // bs + 1]
    q = jnp.asarray(RNG.standard_normal((B, Hkv * G, 1, Dh)).astype("float32"))
    return (q, pools, jnp.asarray(bt), jnp.asarray(pos, jnp.int32),
            [True] * 4 + [False] * sink_lane)


@pytest.mark.parametrize("case", [
    pytest.param(dict(bits=8, group_size=0), id="8-0"),
    pytest.param(dict(bits=8, group_size=16), id="8-16"),
    pytest.param(dict(bits=4, group_size=0), id="4-0"),
    pytest.param(dict(bits=4, group_size=16), id="4-16"),
    pytest.param(dict(heads=(8, 3)), id="hkv8-g3-tile-edges"),
    pytest.param(dict(heads=(4, 12)), id="hkv4-g12-tile-edges"),
    pytest.param(dict(heads=(1, 12)), id="hkv1-g12-tile-edges"),
    pytest.param(dict(heads=(4, 12), bits=4, group_size=16),
                 id="hkv4-g12-int4-g16"),
    pytest.param(dict(heads=(8, 3), soft_cap=2.0), id="hkv8-g3-soft-cap"),
    pytest.param(dict(heads=(4, 12), sink_lane=True), id="hkv4-g12-sink-lane"),
])
def test_paged_kernel_matches_ref(case):
    """Pallas paged flash-decoding (live pages of the scalar-prefetched
    block table, many pages and every KV head per tile) vs the
    gather-then-contiguous jnp oracle; a released lane reads nothing and
    writes a finite output."""
    bits, gs = case.get("bits", 8), case.get("group_size", 0)
    soft_cap = case.get("soft_cap", 0.0)
    if "heads" in case:
        q, pools, bt, pos, live = _tile_edge_case(
            *case["heads"], bits, gs, case.get("sink_lane", False))
    else:
        q, pools, bt, pos, live = _small_table_case(bits, gs)
    o_ref = kv_paged_attn_ref(q, *pools, bt, pos, bits=bits, group_size=gs,
                              soft_cap=soft_cap)
    o_pl = kv_paged_decode_attention(q, *pools, bt, pos, bits=bits,
                                     group_size=gs, soft_cap=soft_cap)
    o_pl = np.asarray(o_pl, np.float32)
    assert np.isfinite(o_pl).all()
    assert (o_pl[~np.asarray(live)] == 0).all()     # nothing read, 0 out
    np.testing.assert_allclose(np.asarray(o_ref, np.float32)[live],
                               o_pl[live], rtol=1e-5, atol=1e-5)


def test_paged_gather_equals_contiguous():
    """A block table laid out 0..n gathers back the contiguous cache, and
    the paged oracle equals the contiguous oracle on it."""
    B, Hkv, S, Dh, bs, H = 2, 2, 64, 16, 16, 4
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, Dh)).astype("float32"))
    # lay the contiguous cache into a pool, slot b owning blocks b*4..b*4+3
    pool = k.reshape(B, Hkv, S // bs, bs, Dh).transpose(0, 2, 1, 3, 4) \
            .reshape(B * (S // bs), Hkv, bs, Dh)
    bt = jnp.arange(B * (S // bs), dtype=jnp.int32).reshape(B, S // bs)
    np.testing.assert_array_equal(np.asarray(gather_paged_kv(pool, bt)),
                                  np.asarray(k))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(k * 0.5)
    pq, psc = quantize_kv(pool), None
    pqv, pvs = quantize_kv(pool * 0.5)
    q = jnp.asarray(RNG.standard_normal((B, H, 1, Dh)).astype("float32"))
    pos = jnp.asarray([40, 63], jnp.int32)
    o_c = kv_attn_ref(q, kq, ks, vq, vs, pos)
    o_p = kv_paged_attn_ref(q, pq[0], pq[1], pqv, pvs, bt, pos)
    np.testing.assert_allclose(np.asarray(o_c, np.float32),
                               np.asarray(o_p, np.float32), rtol=1e-6,
                               atol=1e-6)


# ------------------------------------------------------------- allocator

def test_allocator_prefix_trie_walk_hand_computed():
    """Hit/miss accounting matches a hand-computed trie walk: only full
    blocks strictly before the last prompt token are shareable; the chain
    hash makes the match positional, not content-only."""
    a = BlockAllocator(num_blocks=32, block_size=4)
    p1 = list(range(100, 113))          # 13 tokens → 3 shareable blocks
    b1, pfx1 = a.allocate(p1, max_new=4, max_len=64)
    assert pfx1 == 0 and len(b1) == 5   # ceil((13+4)/4)
    assert (a.prefix_hits, a.prefix_misses) == (0, 3)
    # same first 8 tokens, diverges in block 2 → 2 hits, 1 miss
    p2 = p1[:8] + [1, 2, 3, 4, 5]
    b2, pfx2 = a.allocate(p2, max_new=4, max_len=64)
    assert pfx2 == 8 and b2[:2] == b1[:2] and b2[2] != b1[2]
    assert (a.prefix_hits, a.prefix_misses) == (2, 4)
    assert a.ref[b1[0]] == 2            # shared block ref-counted
    # same CONTENT in block 0 but shifted position → no hit (chain hash)
    p3 = [0] + p1[:7]
    b3, pfx3 = a.allocate(p3, max_new=1, max_len=64)
    assert pfx3 == 0
    assert (a.prefix_hits, a.prefix_misses) == (2, 5)
    a.free_request(b1)
    a.free_request(b2)
    a.free_request(b3)
    a.assert_quiescent()


def test_allocator_cached_blocks_survive_owner():
    """Prefix reuse survives the first owner's lifetime: freed shareable
    blocks park in the cached LRU pool and a later identical prompt revives
    them without re-prefill."""
    a = BlockAllocator(num_blocks=16, block_size=4)
    p = list(range(1, 10))              # 9 tokens → 2 shareable blocks
    b1, _ = a.allocate(p, max_new=2, max_len=64)
    a.free_request(b1)
    assert not a.ref and len(a.cached) == 2
    b2, pfx = a.allocate(p, max_new=2, max_len=64)
    assert pfx == 8 and b2[:2] == b1[:2]
    a.free_request(b2)
    a.assert_quiescent()


def test_allocator_exhaustion_is_atomic():
    """A failing allocation must not leak partial reservations — including
    the shared-cached-revival corner (a cached shared block is not 'still
    available' once revived)."""
    a = BlockAllocator(num_blocks=6, block_size=4)      # 5 allocatable
    p = list(range(1, 13))                              # 3 blocks, 2 shareable
    b1, _ = a.allocate(p, max_new=0, max_len=64)
    a.free_request(b1)                                  # 2 cached + 3 free
    b2, _ = a.allocate(p[:8], max_new=4, max_len=64)    # revives 1 + takes 2
    with pytest.raises(MemoryError):
        a.allocate(list(range(50, 62)), max_new=8, max_len=64)  # needs 5
    hits, misses = a.prefix_hits, a.prefix_misses
    with pytest.raises(MemoryError):                    # retry: same counts
        a.allocate(list(range(50, 62)), max_new=8, max_len=64)
    assert (a.prefix_hits, a.prefix_misses) == (hits, misses)
    a.free_request(b2)
    a.assert_quiescent()


def test_allocator_reregistration_keeps_trie_consistent():
    """A hash can be re-registered while its OLD block still sits cached
    (the chain broke earlier — the head was evicted — so the walk never
    reached it): the old block must be unhooked at registration, or its
    later reclaim tears down the NEW block's live trie entry and the new
    block's own reclaim then KeyErrors (regression: crashed the engine
    under pool pressure)."""
    a = BlockAllocator(num_blocks=10, block_size=4)     # 9 allocatable
    p = list(range(1, 10))                              # 2 shareable blocks
    b1, _ = a.allocate(p, max_new=0, max_len=64)
    a.free_request(b1)                                  # h0, h1 blocks cached
    # evict ONLY the chain head: an 8-block unshareable request (4-token
    # prompt → nothing registered) drains free (7) + the LRU cached head
    b2, _ = a.allocate([91, 92, 93, 94], max_new=28, max_len=64)
    assert b1[0] in b2 and b1[1] not in b2              # old h1 block cached
    a.free_request(b2)                                  # all straight to free
    # re-admit p: h0 misses → h0 AND h1 re-register from the free list
    # while the old h1 block still sits cached (stale reverse mapping)
    b3, pfx = a.allocate(p, max_new=0, max_len=64)
    assert pfx == 0                     # head was evicted → full re-prefill
    # reclaim the stale old-h1 block ...
    b4, _ = a.allocate([81, 82, 83, 84], max_new=20, max_len=64)
    a.free_request(b3)
    a.free_request(b4)
    # ... then churn enough to reclaim the NEW h1 block too — pre-fix this
    # raised KeyError in _take (its trie entry was already torn down)
    b5, _ = a.allocate([71, 72, 73, 74], max_new=32, max_len=64)
    a.free_request(b5)
    assert set(a.trie.values()) == set(a.block_hash)
    a.assert_quiescent()


def test_chain_hash_positional():
    h1 = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4, 2)
    h2 = chain_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4, 2)
    assert h1[0] == h2[0] and h1[1] != h2[1]


# --------------------------------------------------- engine: equivalence

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_engine_paged_matches_dense(params, kv_dtype):
    """Greedy decode tokens identical with KVCacheConfig.paged on/off —
    the e2e smoke for every KV dtype (CI fast tier)."""
    assert _run(_engine(params, kv_dtype)) == _dense_ref(params, kv_dtype)


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_engine_paged_fallback_matches_pallas(params, kv_dtype):
    """use_pallas=False (gather + jnp oracle read) is decode-equivalent to
    the scalar-prefetch Pallas kernel."""
    o_pl = _run(_engine(params, kv_dtype, use_pallas=True),
                prompts=PROMPTS[:2], max_new=6)
    o_np = _run(_engine(params, kv_dtype, use_pallas=False),
                prompts=PROMPTS[:2], max_new=6)
    assert o_pl == o_np


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_preemption_requeue_matches_unconstrained(params, kv_dtype):
    """A pool too small for the workload preempts (evict + requeue) instead
    of crashing, and the multi-slot greedy outputs still match the
    unconstrained dense run exactly."""
    eng = _engine(params, kv_dtype, kv_block_size=4, kv_pool_blocks=7)
    out = _run(eng)
    assert out == _dense_ref(params, kv_dtype)
    assert eng.preemptions > 0
    assert eng.kv_pool_utilization == 1.0
    eng.allocator.assert_quiescent()        # every block freed after run_all


def test_paged_pool_and_block_table_layout(params):
    eng = _engine(params, "int4")
    _run(eng, prompts=[PROMPTS[0]], max_new=3)
    st = eng.state["stack"][0]["u0"]
    NB = eng.num_blocks
    bs = eng.kvcfg.block_size
    assert st["k_q"].shape[1:] == (NB, CFG.n_kv_heads, bs, CFG.hd // 8)
    assert st["k_q"].dtype == jnp.int32
    assert st["k_s"].shape[1:] == (NB, CFG.n_kv_heads, bs, 1)
    bt = np.asarray(eng.state["block_table"])
    assert bt.shape == (2, eng.ecfg.max_len // bs)
    assert (bt == SINK).all()               # finished slots point at the sink


# --------------------------------------------------- engine: prefix cache

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefix_cache_outputs_unchanged(params, kv_dtype):
    """Two requests sharing a ≥1-block system prompt: the second prefills
    only its tail (prefix_hit_rate > 0) and both outputs match the cold
    (prefix_cache=False) engine exactly."""
    sysp = list(range(1, 21))               # 20 tokens → 1 shareable block
    ps = [sysp + [40, 41], sysp + [50, 51, 52]]
    cold_eng = _engine(params, kv_dtype, prefix_cache=False)
    cold = _run(cold_eng, prompts=ps, max_new=6)
    assert cold_eng.prefix_hit_rate == 0.0
    warm_eng = _engine(params, kv_dtype)
    warm = _run(warm_eng, prompts=ps, max_new=6)
    assert warm == cold
    assert warm_eng.prefix_hit_rate > 0
    warm_eng.allocator.assert_quiescent()


def test_same_round_prefix_hit_reads_written_blocks(params):
    """Group-ordering hazard (regression): in one admission round, D (old
    cached prefix) creates group (16, 32) first, then A registers fresh
    sysA blocks, then B's walk hits A's just-registered blocks and joins
    D's *earlier* group.  Groups must dispatch in ascending prefix_len
    order (reader prefix_len > writer prefix_len along a chain — a
    topological order), else B's gather reads A's still-zero pool blocks
    and silently emits wrong tokens.  Outputs must match the
    prefix_cache=False engine exactly AND B's same-round hit must count."""
    sysD, sysA = list(range(1, 33)), list(range(60, 92))
    eng = _engine(params, "bf16", slots=3)
    r0 = eng.submit(sysD + [40, 41], max_new=4)
    eng.run_all()                               # seeds D's cached prefix
    reqs = [sysD + [42, 43], sysA + [50, 51], sysA + [52, 53]]
    rids = [eng.submit(p, max_new=5) for p in reqs]
    outs = eng.run_all()
    cold = _engine(params, "bf16", slots=3, prefix_cache=False)
    c0 = cold.submit(sysD + [40, 41], max_new=4)
    cold.run_all()
    crids = [cold.submit(p, max_new=5) for p in reqs]
    couts = cold.run_all()
    assert [outs[r] for r in rids] == [couts[r] for r in crids]
    assert eng.allocator.prefix_hits == 4       # D: 2 old + B: 2 same-round
    eng.allocator.assert_quiescent()


def test_prefix_hits_across_request_lifetimes(params):
    """The second request arrives after the first finished — its prefix
    blocks come from the cached (ref 0) pool, not from a live request."""
    sysp = list(range(1, 33))               # 32 tokens → 1 shareable block
    eng = _engine(params, "bf16", slots=1)
    r1 = eng.submit(sysp + [40], max_new=3)
    o1 = eng.run_all()
    assert not o1[r1].unfinished
    r2 = eng.submit(sysp + [50, 51], max_new=3)
    eng.run_all()
    assert eng.allocator.prefix_hits == 2   # exactly the two sysp blocks
    eng.allocator.assert_quiescent()


# --------------------------------------------------------- engine: cancel

def test_cancel_queued_and_running(params):
    eng = _engine(params, "bf16")
    r1 = eng.submit(PROMPTS[0], max_new=20)
    r2 = eng.submit(PROMPTS[1], max_new=20)
    r3 = eng.submit(PROMPTS[3], max_new=5)      # queued behind 2 slots
    for _ in range(2):
        eng.step()
    assert eng.cancel(r3)                       # queued: never ran
    assert eng.cancel(r1)                       # running: slot + blocks free
    outs = eng.run_all()
    assert outs[r1].cancelled and outs[r1].unfinished
    assert outs[r3].cancelled and len(outs[r3]) == 0
    assert not outs[r2].cancelled and len(outs[r2]) == 20
    assert not eng.cancel(r1)                   # already finished → False
    assert not eng.cancel(9999)                 # unknown rid
    eng.allocator.assert_quiescent()


def test_cancel_dense_engine(params):
    """cancel() also works on the dense slab (slot freed, no allocator)."""
    eng = _engine(params, "bf16", paged=False)
    r1 = eng.submit(PROMPTS[0], max_new=20)
    eng.step()
    assert eng.cancel(r1)
    outs = eng.run_all()
    assert outs[r1].cancelled


# ------------------------------------------------------------ validation

def test_paged_validation(params):
    with pytest.raises(ValueError, match="divide"):
        _engine(params, "bf16", kv_block_size=48)   # 64 % 48 != 0
    from repro.models import stack as S
    with pytest.raises(ValueError, match="plain attention"):
        S.layer_state(CFG, "ssd", 1, 64, KVCacheConfig(paged=True), 5)
    eng = _engine(params, "bf16", kv_block_size=16, kv_pool_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(list(range(1, 50)), max_new=16)  # needs 4 > 2 allocatable
