"""device_idle_share.admit (%, device): 1 − the union of the device's op
intervals over the traced window, in the admission-bound cells.  Moves
``itl_p95_ms``."""
from lib import trace


def read(ctx):
    return None if ctx.trace is None else trace.idle_share(ctx.trace)
