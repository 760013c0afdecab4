"""Share of decode lanes that carried a live request over the traced decode
steps: 1 - idle slot-steps / (decode steps x capacity), from the
scheduler's ContinuousStats counters.  Moves output_tok_s."""


def read(ctx):
    steps = [s for s in ctx.steps if s.decode_steps]
    if not steps:
        return None
    lanes = len(steps) * ctx.serve["capacity"]
    return 100.0 * (1.0 - sum(s.idle_slot_steps for s in steps) / lanes)
