"""device_idle_share.decode (%, device): 1 − the union of the device's op
intervals over the traced window, in the decode cells.  Moves
``output_tok_s``."""
from lib import trace


def read(ctx):
    return None if ctx.trace is None else trace.idle_share(ctx.trace)
