"""Roofline time of the work each projection requires (M = the rows the
program passes; at most the bucket in decode) over the device time of the
events that implement the projections, in the prefill programs of the window,
in % (device trace, bench/work.py)."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    return ctx.trace.gemm_roofline("prefill", ctx.peaks)
