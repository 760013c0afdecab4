"""The fused W8A8 MVM kernel's share of its roofline in the decode cell:
the least time of every weight matmul of the traced decode steps (each
call: 2*M*K*N ops at the int8 peak, or int8 weight plus bf16 activation
bytes at HBM bandwidth, whichever is larger; M the slot capacity) over
the kernel's device time there.  The matmuls are the configuration's
family's (``token_matmuls``).  Moves output_tok_s."""
import workcount


def read(ctx):
    secs = ctx.trace.kernel_seconds.get(("decode", "fused_mvm"), 0.0)
    steps = sum(s.decode_steps for s in ctx.steps)
    if not secs or not steps:
        return None
    one = workcount.mvm_calls(ctx.serve["capacity"], ctx.family, ctx.conf,
                              ctx.peaks)
    return 100.0 * steps * one.min_seconds / secs
