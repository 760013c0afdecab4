"""Device time of one decode step: the decode cell's module time in the
trace over its executions.  Moves output_tok_s: decode-bound chat serves
about one token per active slot per step."""


def read(ctx):
    n = ctx.trace.module_count.get("decode", 0)
    if not n:
        return None
    return 1e3 * ctx.trace.module_seconds["decode"] / n
