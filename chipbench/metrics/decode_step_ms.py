"""decode_step_ms (ms, runner / model step): device time of the decode
program's executions wholly inside the traced window (the programs in which
``ttq_paged_decode_attention`` runs) over the decode steps they ran —
executions × the mean K per ``decode_block`` call of the window (K=1 when
the degradation ladder swaps in its small program).  Moves
``output_tok_s``."""
from lib import trace


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = trace.program_time(ctx.trace, "ttq_paged_decode_attention")
    calls = ctx.counters.get("runner.decode_block", 0)
    if not n or not calls:
        return None
    mean_k = ctx.counters["decode_steps"] / calls
    return 1e3 * secs / (n * mean_k)
