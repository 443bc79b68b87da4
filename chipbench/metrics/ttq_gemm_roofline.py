"""ttq_gemm_roofline (%, kernels): the least time the chip could take for
the ``ttq_gemm`` calls in the traced window over their device time.

Per call (decode: one per quantized linear per layer per step, x of
max_slots rows): FLOPs 2·B·d_in·d_out; bytes the packed codes
(d_in·d_out·bits/8), f32 scale and zero (2·4·d_in/g·d_out), the f32
1/D vector (4·d_in), x in and y out in bf16 (2·B·(d_in + d_out)).  The
least time is the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth; at decode batch sizes the bytes bound it.  Calls are counted
from the trace's kernel events, L × 6 linears per decode step.  Moves
``output_tok_s``."""
from lib import trace


def cost(B, d_in, d_out, bits, g):
    """(flops, bytes) of one ttq_gemm call."""
    flops = 2.0 * B * d_in * d_out
    nbytes = (d_in * d_out * bits / 8 + 2 * 4 * d_in / g * d_out
              + 4 * d_in + 2 * B * (d_in + d_out))
    return flops, nbytes


def step_min_s(d, B, bits, g, peaks):
    """Least seconds of one decode step's ttq_gemm calls."""
    t = 0.0
    for _, d_out, d_in in d.linears():
        f, b = cost(B, d_in, d_out, bits, g)
        t += max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
    return d.L * t


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = trace.kernel_time(ctx.trace, "ttq_gemm")
    if not n or secs <= 0:
        return None
    steps = n / (ctx.d.L * len(ctx.d.linears()))
    least = steps * step_min_s(ctx.d, ctx.slots, ctx.bits, ctx.group,
                               ctx.peaks)
    return 100.0 * least / secs
