"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Each kernel of the serving path is lowered and compiled by the TPU compiler
at minitron-4b's widths (d 3072, d' 9216, head_dim 128, g 32, 24/8 heads)
for a v5e that is described, not attached: this catches block-tiling and
VMEM refusals that interpret mode on the CPU cannot see.  Nothing runs.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

D, DFF, G = 3072, 9216, 32
H, HKV, DH = 24, 8, 128
SLOTS, MAX_LEN, BLOCK = 4, 1024, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip; keep it out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("d,dp", [(D, DFF), (DFF, D)])
def test_ttq_gemm_compiles(one_chip, bits, d, dp):
    from repro.kernels.ttq_gemm import ttq_gemm
    per = 32 // bits
    fn = functools.partial(ttq_gemm, bits=bits, group_size=G, interpret=False)
    text = _compile(fn, [((SLOTS, d), jnp.bfloat16),
                         ((d // per, dp), jnp.int32),
                         ((d // G, dp), jnp.float32),
                         ((d // G, dp), jnp.float32),
                         ((d,), jnp.float32)], one_chip)
    assert _smoke().kernel_calls(text) == {"ttq_gemm": 1}


@pytest.mark.parametrize("bits", [4, 8])
def test_ttq_quantize_compiles(one_chip, bits):
    from repro.kernels.ttq_quantize import ttq_quantize
    fn = functools.partial(ttq_quantize, bits=bits, group_size=G,
                           interpret=False)
    _compile(fn, [((DFF, D), jnp.bfloat16), ((D,), jnp.float32)], one_chip)


def test_ttq_quantize_shard_width_compiles(one_chip):
    """d = d_ff / 4, a column-parallel shard: 2304 does not divide by the
    512 default k tile, so the kernel tiles by 256."""
    from repro.kernels.ttq_quantize import ttq_quantize
    fn = functools.partial(ttq_quantize, bits=4, group_size=G,
                           interpret=False)
    _compile(fn, [((D, DFF // 4), jnp.bfloat16), ((DFF // 4,), jnp.float32)],
             one_chip)


@pytest.mark.parametrize("tp,dp,d", [("row", DFF, D), ("col", D, DFF),
                                     (None, HKV * DH, D)])
def test_ttq_quantize_tp_compiles(topo, tp, dp, d):
    """The requant of a stacked 32-layer family on a (1, 4) mesh: the
    kernel shard_map'd over the family's layout, one pallas_call, which
    ``chip_smoke.kernel_calls`` names."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    from repro.launch.mesh import make_ctx
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    pctx = make_ctx(mesh)
    w_spec = {"row": P(None, "model", None), "col": P(None, None, "model"),
              None: P()}[tp]
    W = jax.ShapeDtypeStruct((32, dp, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, w_spec))
    Dd = jax.ShapeDtypeStruct((32, d), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    assert ops.tp_quantize_ok(pctx, tp, W, bits=4, group_size=G)
    fn = functools.partial(ops.ttq_quantize_tp, bits=4, group_size=G,
                           pctx=pctx, tp=tp, interpret=False)
    text = jax.jit(fn).lower(W, Dd).compile().as_text()
    assert _smoke().kernel_calls(text) == {"ttq_quantize": 1}


def _smoke():
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)
    try:
        import chip_smoke
    finally:
        sys.path.remove(root)
    return chip_smoke


@pytest.mark.parametrize("kv", ["int8", "int4"])
def test_dense_decode_attention_compiles(one_chip, kv):
    from repro.core.kvquant import KVCacheConfig
    from repro.kernels.ttq_attn import ttq_decode_attention
    c = KVCacheConfig(dtype=kv)
    fn = functools.partial(ttq_decode_attention, bits=c.bits, interpret=False)
    code = ((SLOTS, HKV, MAX_LEN, c.code_shape(DH)), c.code_dtype)
    scale = ((SLOTS, HKV, MAX_LEN, c.groups(DH)), jnp.float32)
    _compile(fn, [((SLOTS, H, 1, DH), jnp.bfloat16), code, scale, code, scale,
                  ((SLOTS,), jnp.int32)], one_chip)


def test_paged_decode_attention_compiles(one_chip):
    from repro.core.kvquant import KVCacheConfig
    from repro.kernels.ttq_attn import ttq_paged_decode_attention
    c = KVCacheConfig(dtype="int8", paged=True, block_size=BLOCK)
    nblk = MAX_LEN // BLOCK
    nb = SLOTS * nblk + 1
    fn = functools.partial(ttq_paged_decode_attention, bits=c.bits,
                           interpret=False)
    code = ((nb, HKV, BLOCK, c.code_shape(DH)), c.code_dtype)
    scale = ((nb, HKV, BLOCK, c.groups(DH)), jnp.float32)
    _compile(fn, [((SLOTS, H, 1, DH), jnp.bfloat16), code, scale, code, scale,
                  ((SLOTS, nblk), jnp.int32), ((SLOTS,), jnp.int32)], one_chip)
