"""Mean device time of one decode step less the time of the events that
implement its projections: attention, the paged KV gather and write,
norms and the rest (device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.non_gemm_ms("decode")
