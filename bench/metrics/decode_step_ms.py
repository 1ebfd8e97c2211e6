"""Mean device time of one call of the decode-step program in the window
(device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.program_ms("decode")
