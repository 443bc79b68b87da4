"""``chip_smoke.py``'s serving and mesh phases end to end at smoke size.

The script refuses to run without a TPU; these tests call its phases
directly on the CPU backend (interpret-mode kernels) so a control-flow
fault shows here and not first on the chip.  The model keeps minitron-4b's
family and shape ratios at tiny widths; traffic and cache sizes shrink with
it.  The TPU-only part — finding ``tpu_custom_call`` ops — is stood in for
by looking for the kernels' jitted entries in the CPU program text, which
still tells a kernel from the ``kernels/ref.py`` oracle that could take its
place.  The mesh phase runs on four virtual CPU devices.
"""
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

# minitron-4b's smoke shape, widened so every TP family and the attention
# heads split four ways with group-aligned shards (d / 4 = 32 = g)
_SHRINK = """
import dataclasses, re, sys
sys.path.insert(0, {root!r})
import chip_smoke as cs

cs.N_REQUESTS, cs.PROMPT_LEN, cs.MAX_NEW = 6, (8, 40), 4
cs.PROBE_LENS = (8, 12, 16, 20)
flags = list(cs.SERVE_FLAGS)
for opt, val in (("--max-len", "64"), ("--prefill-chunk", "16")):
    flags[flags.index(opt) + 1] = val
cs.SERVE_FLAGS = flags

def cpu_kernel_calls(text):
    # interpret mode leaves no tpu_custom_call; the kernels' jitted
    # entries still name themselves in the ops' metadata
    out = {{}}
    for name in re.findall(r"jit\\((ttq_\\w+)\\)", text):
        out[name] = out.get(name, 0) + 1
    return out

cs.kernel_calls = cpu_kernel_calls
cfg = dataclasses.replace(cs.model_config(), n_layers=2, d_model=128,
                          n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256,
                          vocab=512)
"""


def test_serve_run_smoke_size(subproc):
    """One device: traffic with admissions mid-run, online requant after
    the first admission, zero guard events, kernels in the decode and
    requant programs, finite logits from the engine's own programs."""
    out = subproc(_SHRINK.format(root=ROOT) + """
import jax
cs.serve_run(cfg, jax.devices()[0], cs.CompileClock())
assert not cs.FAILED, cs.FAILED
print("SERVE_OK")
""", timeout=900)
    assert "SERVE_OK" in out and "finite=True" in out
    line = [ln for ln in out.splitlines() if ln.startswith("one chip:")][0]
    assert "ttq_gemm" in line and "ttq_paged_decode_attention" in line
    assert "ttq_quantize" in line.split("requant programs kernels")[1]


def test_mesh_compare_four_devices(mesh_subproc):
    """Four virtual devices: the (1, 4) TP engine against one device —
    prefill and first-decode logits within ``MESH_TOL``, the sharded
    requant on the ttq_quantize kernel."""
    out = mesh_subproc(_SHRINK.format(root=ROOT) + """
import jax
assert jax.device_count() == 4
cs.mesh_compare(cfg, 4)
assert not cs.FAILED, cs.FAILED
print("MESH_OK")
""", timeout=900)
    assert "MESH_OK" in out
    assert "mesh (1, 4): decode program kernels" in out
    line = [ln for ln in out.splitlines()
            if ln.startswith("mesh (1, 4): decode")][0]
    assert "ttq_quantize" in line.split("requant programs kernels")[1]


def test_smoke_refuses_without_tpu(capsys):
    """The script exits non-zero and prints no ``ok`` line off the TPU."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_location(subproc):
    """``enable_compile_cache``: JAX_COMPILATION_CACHE_DIR wins untouched;
    without it the cache goes to the fixed ``.jax_cache/`` in the checkout."""
    out = subproc("""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = "/nonexistent/cache"
import jax
from repro.launch.cache import DEFAULT_DIR, enable_compile_cache
assert enable_compile_cache() == "/nonexistent/cache"
assert jax.config.jax_compilation_cache_dir == "/nonexistent/cache"
del os.environ["JAX_COMPILATION_CACHE_DIR"]
path = enable_compile_cache()
assert path == DEFAULT_DIR == jax.config.jax_compilation_cache_dir
root = os.path.dirname(path)
assert os.path.basename(path) == ".jax_cache"
assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
print("CACHE_OK")
""")
    assert "CACHE_OK" in out
