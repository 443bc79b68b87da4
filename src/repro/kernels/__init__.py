"""Pallas TPU kernels for the paper's compute hot-spots.

* ``ttq_gemm``            — fused int-packed dequant matmul (the Marlin
                            analogue): HBM int4/int8 weights → VMEM
                            unpack+dequant → MXU.
* ``ttq_quantize``        — the per-prompt online quantization as one
                            streaming pass.
* ``kv_decode_attention`` — fused dequant decode-attention over an int8/int4
                            KV cache (flash-decoding over the S axis).
* ``kv_paged_decode_attention`` — the block-paged variant: flash-decoding
                            over a per-slot block table into a shared
                            (num_blocks, Hkv, block_size, ·) quantized pool
                            (each slot's live blocks only, copied through
                            the scalar-prefetched table).

``ops`` wraps all with jnp fallbacks; ``ref`` holds the pure-jnp oracles the
tests assert against (interpret=True on CPU, compiled on TPU).
"""
from .ops import (kv_decode_attention, kv_paged_decode_attention, ttq_gemm,
                  ttq_quantize)

__all__ = ["kv_decode_attention", "kv_paged_decode_attention", "ttq_gemm",
           "ttq_quantize"]
