"""requant_share (%, requant): Δ``TTQEngine.requant_wall_s`` over the
window's seconds — the share of the engine thread's wall time spent
requantizing.  Moves ``itl_p95_ms``: a requant stalls every running
stream."""


def read(ctx):
    e = ctx.engine
    if not e.get("n_requants") or ctx.window_s <= 0:
        return None
    return 100.0 * e["requant_wall_s"] / ctx.window_s
