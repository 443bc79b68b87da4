"""ttq_quantize_roofline (%, kernels): the least time the chip could take
for the ``ttq_quantize`` calls in the traced window over their device time.

One requantization quantizes every quantized linear of every layer; the
fused plan stacks leaves of one (d_out, d_in) shape into a family and
runs one kernel call per family.  Per weight element: bytes the bf16
weight in (2), packed codes out (bits/8) and f32 scale and zero out
(2·4/g), plus the f32 D vector per row tile; FLOPs 5 (scale by D, group
min and max, subtract the zero, divide by the step).  The least time is
the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth; the
bytes bound it.  Requantizations are counted as kernel events over the
number of families.  Moves ``itl_p95_ms``: a requant stalls every
running stream."""
from lib import trace


def cost(d_out, d_in, bits, g, bm=256):
    """(flops, bytes) of quantizing one (d_out, d_in) weight."""
    n = d_out * d_in
    nbytes = n * (2 + bits / 8 + 2 * 4 / g) + 4 * d_in * max(d_out // bm, 1)
    return 5.0 * n, nbytes


def families(d):
    """{(d_out, d_in): layers × members} of one requantization."""
    out = {}
    for _, d_out, d_in in d.linears():
        out[(d_out, d_in)] = out.get((d_out, d_in), 0) + d.L
    return out


def requant_min_s(d, bits, g, peaks):
    t = 0.0
    for (d_out, d_in), count in families(d).items():
        f, b = cost(d_out, d_in, bits, g)
        t += count * max(f / peaks["bf16_flops"],
                         b / peaks["hbm_bytes_per_s"])
    return t


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = trace.kernel_time(ctx.trace, "ttq_quantize")
    if not n or secs <= 0:
        return None
    requants = n / len(families(ctx.d))
    return 100.0 * requants * requant_min_s(ctx.d, ctx.bits, ctx.group,
                                            ctx.peaks) / secs
