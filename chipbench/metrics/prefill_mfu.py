"""prefill_mfu (%, runner / model step): model FLOPs of the prompts whose
first token reached a client in the traced window (lib/flops.py: every
layer on every prompt token, causal attention, the head on the last
token) per second of the window, over the chips' bf16 peak.  Moves
``itl_p95_ms``: a prefill dispatch stalls every running stream."""
from lib import flops


def read(ctx):
    plens = ctx.summary["prefill_plens"]
    if not plens or ctx.window_s <= 0:
        return None
    f = sum(flops.prefill_flops(ctx.d, p) for p in plens)
    return 100.0 * f / ctx.window_s / (ctx.peaks["bf16_flops"] * ctx.chips)
