"""decode_mfu (%, runner / model step): model FLOPs of the decode tokens
that reached clients in the traced window (weight matmuls, the output head
and attention over each token's live context, from shapes: lib/flops.py)
per second of the window, over the chips' bf16 peak.  It bounds the decode
kernels' rooflines: a kernel taken off the path leaves its own metric
silent, not this one.  Moves ``output_tok_s``."""
from lib import flops


def read(ctx):
    ctxs = ctx.summary["decode_ctx"]
    if not ctxs or ctx.window_s <= 0:
        return None
    f = sum(flops.decode_flops(ctx.d, c) for c in ctxs)
    return 100.0 * f / ctx.window_s / (ctx.peaks["bf16_flops"] * ctx.chips)
