"""requant_ms (ms, requant): host milliseconds per requantization in the
window — Δ``TTQEngine.requant_wall_s`` / Δ``n_requants``.  With guards on,
the health gate's host read ends each requant in a sync, so this holds the
device work queued ahead of it too.  Moves ``itl_p95_ms``: a requant
stalls every running stream."""


def read(ctx):
    e = ctx.engine
    if not e.get("n_requants"):
        return None
    return 1e3 * e["requant_wall_s"] / e["n_requants"]
