"""Model FLOPs from shapes, for the step-level ``mfu`` metrics.

Counted: the weight matmuls (2·d_out·d_in per token per linear), attention
(q·kᵀ and p·v, 2·2·H·hd per attended key) and the output head where the
model needs it: one row per generated token.  A prefill's full-vocabulary
logits at every prompt position are computed by the program but are not
model FLOPs, nor are dequantization, norms or softmax.
"""
from __future__ import annotations


def weight_flops(d) -> float:
    """Matmul FLOPs of one token through every layer, without the head."""
    return 2.0 * d.L * sum(o * i for _, o, i in d.linears())


def head_flops(d) -> float:
    return 2.0 * d.V * d.D


def attn_flops(d, keys: int) -> float:
    """Attention FLOPs of one query over ``keys`` keys, all layers."""
    return 4.0 * d.L * d.H * d.hd * keys


def decode_flops(d, ctx: int) -> float:
    """One generated token whose step attends to ``ctx`` keys."""
    return weight_flops(d) + head_flops(d) + attn_flops(d, ctx)


def prefill_flops(d, plen: int) -> float:
    """A prompt of ``plen`` tokens: every layer on every token, causal
    attention (token t attends to t + 1 keys), the head on the last one."""
    return (plen * weight_flops(d) + attn_flops(d, plen * (plen + 1) / 2)
            + head_flops(d))
