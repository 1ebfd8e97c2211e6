"""Real (unpadded) prompt tokens of the prefills admitted inside the
window, per second of window (host clock)."""


def read(ctx):
    return sum(len(ctx.requests[rid].prompt) for rid in ctx.win.prefilled()) / ctx.win.seconds
