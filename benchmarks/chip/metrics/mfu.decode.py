"""The whole step's share of the chip's peak: the least time of all model
work completed in the traced window over the window.  Work: every decode
token through every logical layer and the unembedding, every real prompt
token through every logical layer, one unembedding row per finished
prefill, and the causal attention of both (a decode token is one query
over the keys it sees); the matmuls and the attention call are the
configuration's family's.  Weight matmuls at the int8 peak, attention at
the bf16 peak."""
import workcount


def read(ctx):
    if not ctx.trace.window_s:
        return None
    calls = [c for s in ctx.steps for c in s.prefill]
    dec = len(ctx.decode_ctx)
    prompt = sum(c[2] for c in calls)
    attention = ([(1, keys - 1) for keys in ctx.decode_ctx]
                 + [(c[2], c[1]) for c in calls])
    unembed = dec + sum(1 for c in calls if c[3])
    ideal = workcount.ideal_seconds(dec + prompt, unembed, attention,
                                    ctx.family, ctx.conf, ctx.peaks)
    return 100.0 * ideal / ctx.trace.window_s
