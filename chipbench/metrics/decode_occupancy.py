"""decode_occupancy (%, scheduler): decode tokens of the window over the
slot-steps the decode blocks ran (Σ K per ``runner.decode_block`` call ×
max_slots) — how full the decode batch was.  Counters of the harness's
wrapper; moves ``output_tok_s``."""


def read(ctx):
    c = ctx.counters
    if not c.get("decode_slot_steps"):
        return None
    return 100.0 * c["decode_tokens"] / c["decode_slot_steps"]
